"""The engine tests' request and model, defined once: a prompt, and a
`tiny` whose greedy stream outlives the scenario of the test that uses
it.

`tiny`'s own EOS is token 1, and its greedy continuation of PROMPT
under init_params(key(0)) is [183, 94, 1, ...]: the stream ends at its
third token, inside the first wave, and a test that arms a fault "after
the first token", looks for live KV mid-stream or indexes the sixth
token finds the request already gone. `live_config()` re-points EOS at
a token the stream does not reach within LIVE_TOKENS; a test asks for
at most that many tokens, and tests/test_slot.py's guard fails in one
place when a change of weights, seed or prompt shortens the stream.
"""

import dataclasses

from seldon_tpu.models.config import ModelConfig, get_config

PROMPT = list(range(2, 26))  # 24 tokens: 3 kv_blocks of 8 exactly
# More than the scheduler can have in flight when a first token reaches
# the client (an admission wave and up to four more chunks of 4 steps: 21
# tokens), so a test that acts "after the first token" still finds waves
# to come; and PROMPT + LIVE_TOKENS fits the tests' 64-token window.
LIVE_TOKENS = 32


def live_config(preset: str = "tiny", **replace) -> ModelConfig:
    cfg = get_config(preset)
    return dataclasses.replace(
        cfg, eos_token_id=cfg.vocab_size - 1, **replace)
