"""The family of SDAR-30B-A3B-Chat (`model_type` sdar_moe): a Qwen3-MoE
block (pre-norm grouped-query attention with QK-norm, a softmax-routed
sparse SwiGLU in every layer, untied head) that generates by DIFFUSION
OVER BLOCKS: attention is block-causal, and a block of Bk positions is
denoised in place before its KV is final. Served from the program's
seeded bf16 tree.

Keys of a configuration file of this family (Hugging Face names, values
as run): hidden_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, vocab_size, max_position_embeddings,
rms_norm_eps, rope_theta, moe_intermediate_size, num_experts,
num_experts_per_tok, norm_topk_prob (must be true), mlp_only_layers (must
be empty), decoder_sparse_step (1), tie_word_embeddings (false),
attention_bias (false); `assumed` qk_norm (true), block_length,
denoise_steps, remask, denoise_threshold, mask_token_id: the generation
procedure, which the published config.json does not give; `serving`
weight_dtype / kv_cache_dtype (bf16) and kv_budget_tokens.

The equations (x is [S, D]; eps = rms_norm_eps; blk(i) = i // Bk):

  h = rms(x, op_norm);  q = rope(rms_head(h Wq, q_norm));
  k = rope(rms_head(h Wk, k_norm));  v = h Wv
  a_i = softmax_j(q_i . k_j / sqrt(head_dim) : blk(j) <= blk(i)) v_j
  x = x + a Wo
  h = rms(x, ff_norm);  p = softmax(h Wr) over the experts;  T = top-k(p);
  g_e = p_e / sum_T p;  x = x + sum_{e in T} g_e (silu(h Wg_e) * (h Wu_e)) Wd_e
  logits = rms(x_L, final_norm) Wh      (row i scores position i itself)

Generation of one request (`generate`): the prompt's whole blocks are
context; the first block in hand is the prompt's tail (decided) followed
by undecided positions, every later block starts undecided. An undecided
position's input is the embedding of mask_token_id. A denoising pass runs
the block against everything before it and itself, takes x0_i = argmax_i
with the mask id excluded and its confidence c_i = softmax(logits_i)[x0_i],
and decides k = Bk // denoise_steps of the undecided positions (all that
are left if fewer): the leftmost ("sequential"), or those of highest
confidence, ties to the left, and every one above the threshold when at
least k are ("low_confidence"). A decided position never changes. When
none is undecided the block is final: its tokens past the prompt are
emitted, cut after the first EOS and at the budget.

The reference follows these in straightforward jax.numpy: float32 under
jax.default_matmul_precision("highest"), a Python loop over layers and
over experts (every expert on every token, masked by its weight: no
dispatch), no cache (every pass is a forward over the whole sequence so
far), no scan, no kernels, no code of seldon_tpu/models.

`forward_logits` is the teacher-forced form the harness's parity needs
(benchmark/reference.py judges row len(prompt) - 1 + i against the
engine's i-th token): row r holds the logits from which position r + 1
was DECIDED under the "sequential" rule, that is the logits of position
r + 1 in the forward of seq[:g] + mask x (block end - g), g the first
position of the group of k that position was decided in. That is a
function of the sequence alone where the prompt ends on a block
(`prompt_len` None: the harness's probes, whose lengths are multiples of
the block); a prompt that ends inside a block shifts its first block's
groups, and a caller that knows its length passes it
(tools/parity_gaps.py). The last row is of position S, one past the
sequence: its group's forward needs no token the sequence lacks.

Its lower-precision twin, the negative control: the same with every
layer's matrices (not norms, router, embedding or head) rounded to
float8 e4m3, the nearest precision below the served bf16.

The costs price what a PASS needs: `rows` is what the harness hands
decode_step_cost, tokens a pass; live slots = rows x (steps + 1) / Bk.

run.py loads this file and never imports JAX, so JAX is imported by the
functions that compute (_need_jax), not by the module."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

CONTROL = "float8 e4m3 grid"
ATTN = "full_attention"
HEAD_ROWS = 16384  # vocabulary rows of the head multiplied at a time


# -- the configuration's keys as the program's ModelConfig -------------------

def procedure(cfg: Dict) -> Dict:
    """The generation procedure's five sizes, from `assumed`."""
    a = cfg["assumed"]
    return {"gen_block": int(a["block_length"]),
            "denoise_steps": int(a["denoise_steps"]),
            "remask": a["remask"],
            "denoise_threshold": a.get("denoise_threshold"),
            "mask_token_id": int(a["mask_token_id"])}


def model_config_kwargs(cfg: Dict) -> Dict:
    """The benchmark's configuration file (HF key names) as keyword
    arguments of seldon_tpu.models.config.ModelConfig. Every value is
    what a JSON round trip of the program's config gives back, which is
    how run.check_metadata compares."""
    serving = cfg.get("serving", {})
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer of this family is sparse")
    if not cfg["norm_topk_prob"] or cfg.get("attention_bias") \
            or cfg.get("tie_word_embeddings"):
        raise ValueError("this family renormalises its top-k, has no "
                         "attention bias and an untied head")
    return dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=False,
        n_experts=int(cfg["num_experts"]),
        n_experts_per_token=int(cfg["num_experts_per_tok"]),
        d_ff_expert=int(cfg["moe_intermediate_size"]),
        router="softmax",
        router_norm_topk=True,
        qk_norm=bool(cfg["assumed"].get("qk_norm", True)),
        layer_types=[ATTN] * cfg["num_hidden_layers"],
        weight_dtype=serving.get("weight_dtype", "bf16"),
        kv_cache_dtype=serving.get("kv_cache_dtype", "bf16"),
        **procedure(cfg),
    )


# -- the plain reference ------------------------------------------------------

def _need_jax() -> None:
    global jax, jnp
    import jax
    import jax.numpy as jnp


def build_params(cfg: Dict, seed: int):
    """The tree the unit serves: the program's seeded bf16 initialiser."""
    _need_jax()
    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.transformer import init_params

    if cfg["serving"]["weight_dtype"] != "bf16":
        raise ValueError("this family is served, and read, in bf16")
    model = ModelConfig(**model_config_kwargs(cfg)).validate()
    return init_params(model, jax.random.key(int(seed)))


def _layers(params):
    """The tree's layers in layer order (the program stores them stacked
    by segment, repeat and position within the period)."""
    for period in params["segments"]:
        reps = next(iter(period[0].values())).shape[0]
        for r in range(reps):
            for pos in period:
                yield {k: v[r] for k, v in pos.items()}


def _mat(w, control):
    """A layer's matrix in float32; control: rounded to the float8 e4m3
    grid (4 significant bits, normal down to 2^-6, then steps of 2^-9,
    largest 448), written out in arithmetic so that no compiler drops it
    as excess precision (families/lfm2.py found one that did)."""
    w = w.astype(jnp.float32)
    if control:
        exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(w), 2.0 ** -20)))
        step = jnp.exp2(jnp.maximum(exp, -6.0) - 3.0)
        w = jnp.clip(jnp.round(w / step) * step, -448.0, 448.0)
    return w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [N, S, H, Dh]; position s rotates pair (i, i + Dh/2) by
    s * theta^(-2i/Dh)."""
    s, dh = x.shape[1], x.shape[3]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(x, lw, dims, control):
    """x [N, S, D]: N sequences, each under the block-causal mask."""
    n_heads, n_kv, dh, theta, eps, bk = dims
    n, s, _ = x.shape
    h = _rms(x, lw["op_norm"], eps)
    q = (h @ _mat(lw["wq"], control)).reshape(n, s, n_heads, dh)
    k = (h @ _mat(lw["wk"], control)).reshape(n, s, n_kv, dh)
    v = (h @ _mat(lw["wv"], control)).reshape(n, s, n_kv, dh)
    if "q_norm" in lw:  # QK-norm: per head over head_dim, before RoPE
        q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv  # each kv head serves rep query heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("nshd,nthd->nhst", q, k) / jnp.sqrt(jnp.float32(dh))
    blk = jnp.arange(s) // bk
    sees = blk[None, :] <= blk[:, None]  # whole own block, all before
    p = jax.nn.softmax(jnp.where(sees[None, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("nhst,nthd->nshd", p, v).reshape(n, s, n_heads * dh)
    return x + out @ _mat(lw["wo"], control)


def _route(x, lw, eps, top_k):
    """softmax over all experts, top-k, renormalised over the k."""
    h = _rms(x, lw["ff_norm"], eps)
    p = jax.nn.softmax(h @ lw["router"].astype(jnp.float32), axis=-1)
    top_p, top_idx = jax.lax.top_k(p, top_k)
    return h, top_idx, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def _expert_add(acc, h, top_idx, top_w, e, gate, up, down, control):
    """acc += (weight of expert e for each token, 0 where not routed) * expert_e(h)."""
    w_e = jnp.sum(jnp.where(top_idx == e, top_w, 0.0), axis=-1)
    y = (jax.nn.silu(h @ _mat(gate, control)) * (h @ _mat(up, control))) \
        @ _mat(down, control)
    return acc + w_e[..., None] * y


def _dims(cfg: Dict):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]), procedure(cfg)["gen_block"])


def _hidden(params, tokens, known, cfg: Dict, control: bool):
    """The final-normed hidden states [N, S, D] of N sequences `tokens`
    [N, S] int32, a position's input being the mask's embedding where
    `known` [N, S] is False."""
    dims = _dims(cfg)
    eps = dims[4]
    attention = jax.jit(_attention, static_argnums=(2, 3))
    route = jax.jit(_route, static_argnums=(2, 3))
    expert_add = jax.jit(_expert_add, static_argnums=(8,))
    ids = jnp.where(known, tokens, procedure(cfg)["mask_token_id"])
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    n_layers = 0
    for lw in _layers(params):
        x = attention(x, lw, dims, control)
        h, top_idx, top_w = route(x, lw, eps, int(cfg["num_experts_per_tok"]))
        acc = jnp.zeros_like(x)
        for e in range(int(cfg["num_experts"])):
            acc = expert_add(acc, h, top_idx, top_w, e, lw["w_gate"][e],
                             lw["w_up"][e], lw["w_down"][e], control)
        x = x + acc
        n_layers += 1
    if n_layers != cfg["num_hidden_layers"]:
        raise ValueError(f"the tree has {n_layers} layers, the file "
                         f"{cfg['num_hidden_layers']}")
    return _rms(x, params["final_norm"], eps)


def _head(params, x):
    """x [R, D] -> logits [R, V], the head multiplied HEAD_ROWS of the
    vocabulary at a time so that its float32 copy fits beside the tree."""
    head = params["lm_head"]  # [D, V] bf16
    parts = [x @ head[:, v:v + HEAD_ROWS].astype(jnp.float32)
             for v in range(0, head.shape[1], HEAD_ROWS)]
    return jnp.concatenate(parts, axis=-1)


def decided_from(position: int, prompt_len: int, bk: int, k: int) -> int:
    """g: under the "sequential" rule, the first position of the group of
    k that `position` (>= prompt_len) was decided in; everything before g
    was decided when its logits were taken, g .. its block's end was not."""
    start = position // bk * bk
    first = max(start, min(prompt_len, start + bk))  # first undecided one
    return first + (position - first) // k * k


class _DecidedFrom:
    """forward_logits' [S, V] rows, computed when they are sliced: every
    caller slices the prompt's rows off before it reads a value
    (benchmark/reference.logit_gaps: `[len(prompt) - 1:]`), and a row
    costs a forward of its own group over all 128 experts, so the rows
    nobody reads are never computed (the harness's 12 of 131: a parity
    child of 40 s where all rows took 350 s on the chip, PR 52). Indexing
    gives a float32 jax array; rows already computed are kept."""

    def __init__(self, rows_from, n_rows: int, vocab: int):
        self._rows_from, self.shape = rows_from, (n_rows, vocab)
        self._have = {}

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, idx):
        want = range(self.shape[0])[idx] if isinstance(idx, slice) \
            else [range(self.shape[0])[idx]]
        missing = [r for r in want if r not in self._have]
        if missing:
            for r, row in zip(missing, self._rows_from(missing)):
                self._have[r] = row
        rows = jnp.stack([self._have[r] for r in want])
        return rows if isinstance(idx, slice) else rows[0]

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        return np.asarray(self[:], dtype)


def forward_logits(params, tokens, cfg: Dict, control: bool = False,
                   prompt_len: Optional[int] = None):
    """[S, V] float32 for the sequence `tokens` [S]: row r holds the
    logits position r + 1 was decided from under the "sequential" rule
    (module docstring), the mask id's column lowered to the row's least
    value (the procedure never decides on it; finite, so that a caller
    may average a row). `prompt_len`: where the generated part starts
    (None: on a block). control: the layers' matrices on the float8 e4m3
    grid. The rows are computed when sliced (_DecidedFrom)."""
    _need_jax()
    proc = procedure(cfg)
    bk, k = proc["gen_block"], proc["gen_block"] // proc["denoise_steps"]
    seq = [int(t) for t in tokens]
    S = len(seq)
    plen = 0 if prompt_len is None else prompt_len
    width = (S // bk + 1) * bk

    def rows_from(rows: List[int]):
        # the distinct forwards: one a group, each over its block's end
        groups: Dict[int, List[int]] = {}
        for r in rows:
            groups.setdefault(decided_from(r + 1, plen, bk, k), []).append(r)
        toks, known, at = [], [], []
        for n, (g, rs) in enumerate(sorted(groups.items())):
            toks.append(seq[:g] + [0] * (width - g))
            known.append([True] * g + [False] * (width - g))
            at += [(n, r) for r in rs]
        out = {}
        with jax.default_matmul_precision("highest"):
            # a few forwards at a time: N x S x S scores a head stay small
            per = max(1, 4096 // width)
            for i in range(0, len(toks), per):
                x = _hidden(params, jnp.asarray(toks[i:i + per], jnp.int32),
                            jnp.asarray(known[i:i + per]), cfg, control)
                mine = [(n - i, r) for n, r in at if i <= n < i + per]
                logits = _head(params, x[jnp.asarray([n for n, _ in mine]),
                                         jnp.asarray([r + 1 for _, r in mine])])
                logits = logits.at[:, proc["mask_token_id"]].set(
                    jnp.min(logits, axis=-1))
                out.update({r: logits[j] for j, (_, r) in enumerate(mine)})
        return [out[r] for r in rows]

    return _DecidedFrom(rows_from, S, params["lm_head"].shape[1])


def transfer(known: List[bool], conf: List[float], k: int, rule: str,
             threshold: Optional[float]) -> List[int]:
    """The undecided positions of a block a denoising pass decides."""
    open_ = [i for i, kn in enumerate(known) if not kn]
    if rule == "sequential":
        return open_[:k]
    if threshold is not None:
        above = [i for i in open_ if conf[i] > threshold]
        if len(above) >= k:
            return above
    return sorted(sorted(open_, key=lambda i: (-conf[i], i))[:k])


def generate(params, prompt, n_new: int, cfg: Dict, eos: Optional[int] = None,
             control: bool = False, trace: Optional[list] = None) -> List[int]:
    """The whole procedure for one request, greedy: the tokens emitted
    after `prompt`, at most `n_new`, cut after the first `eos`. `trace`
    (a list) is given one (block start, decided tokens or None) entry a
    pass."""
    _need_jax()
    proc = procedure(cfg)
    bk, k = proc["gen_block"], proc["gen_block"] // proc["denoise_steps"]
    seq = [int(t) for t in prompt]
    start = len(seq) // bk * bk
    out: List[int] = []
    with jax.default_matmul_precision("highest"):
        while True:
            tail = len(seq) - start
            block = seq[start:] + [0] * (bk - tail)
            known = [True] * tail + [False] * (bk - tail)
            while not all(known):
                ids = jnp.asarray([seq[:start] + block], jnp.int32)
                kn = jnp.asarray([[True] * start + known])
                x = _hidden(params, ids, kn, cfg, control)[0, start:]
                logits = _head(params, x)
                logits = logits.at[:, proc["mask_token_id"]].set(-jnp.inf)
                x0 = [int(t) for t in jnp.argmax(logits, axis=-1)]
                conf = [float(c) for c in jnp.max(
                    jax.nn.softmax(logits, axis=-1), axis=-1)]
                for i in transfer(known, conf, k, proc["remask"],
                                  proc["denoise_threshold"]):
                    block[i], known[i] = x0[i], True
                if trace is not None:
                    trace.append((start, list(block), list(known)))
            for tok in block[tail:]:
                out.append(tok)
                if tok == eos or len(out) >= n_new:
                    return out
            seq, start = seq[:start] + block, start + bk


# -- what a pass needs ---------------------------------------------------------

_BYTES = {"bf16": 2}


def attn_params(cfg: Dict) -> int:
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def expert_params(cfg: Dict) -> int:
    """One expert's SwiGLU triple."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def head_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def layer_counts(cfg: Dict) -> Dict[str, int]:
    n = cfg["num_hidden_layers"]
    return {"attention": n, "sparse": n, "dense": 0, "conv": 0}


def sparse_period_repeats(cfg: Dict) -> int:
    """Every layer is the same kind: the program scans one period of one
    layer, num_hidden_layers times."""
    return cfg["num_hidden_layers"]


def experts_touched(cfg: Dict, rows: float) -> float:
    """Expected distinct experts a sparse layer reads for `rows` token
    rows under a uniform router (top-k of E): concave in the rows."""
    n_exp, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return n_exp * (1.0 - (1.0 - k / n_exp) ** max(rows, 0.0))


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one position over every layer."""
    b = _BYTES[cfg["serving"]["kv_cache_dtype"]]
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * b


def passes_per_block(cfg: Dict) -> int:
    return procedure(cfg)["denoise_steps"] + 1


def decode_step_cost(cfg: Dict, rows: float, context: float,
                     touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) one PASS needs. `rows` is what the harness hands
    over: tokens emitted a pass, so the live slots are rows x (steps + 1)
    / Bk, each with Bk positions in the pass. A pass reads the attention
    weights and routers once, `touched` experts a layer, the live slots'
    KV, and (on the steps of steps + 1 passes that denoise) the head; a
    committing slot writes Bk rows of KV. `touched`: the distinct experts
    a sparse layer read a pass, as the unit counted them. Absent (a unit
    that counts none), what slots x Bk x k assignments touch under a
    uniform router, which is far too many here: the undecided positions
    of a pass share the mask's embedding and route alike (28 experts a
    layer counted at 2.5 live slots where a uniform router expects 61:
    PERF.md section 6, PRs 52 to 54), and a concave count priced at the
    mean live slots overstates itself besides (~6 % at three slots)."""
    proc = procedure(cfg)
    bk, steps = proc["gen_block"], proc["denoise_steps"]
    slots = rows * (steps + 1) / bk
    positions = slots * bk
    denoising = steps / (steps + 1.0)
    L, b = cfg["num_hidden_layers"], _BYTES[cfg["serving"]["weight_dtype"]]
    h, dh, k = cfg["num_attention_heads"], cfg["head_dim"], cfg["num_experts_per_tok"]
    flops = positions * 2.0 * (
        L * (attn_params(cfg) + k * expert_params(cfg) + router_params(cfg))
        + denoising * head_params(cfg)) \
        + positions * L * h * 4.0 * dh * (context + bk)
    touched = experts_touched(cfg, positions) if touched is None else touched
    bytes_ = (b * L * (attn_params(cfg) + touched * expert_params(cfg))
              + 4 * L * router_params(cfg)
              + b * denoising * head_params(cfg)
              + slots * context * kv_bytes_per_token(cfg)
              + slots * bk * kv_bytes_per_token(cfg) / (steps + 1.0))
    return flops, bytes_


def grouped_product_cost(cfg: Dict, rows: float,
                         touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) ONE grouped product (one of gate / up / down of one
    sparse layer) needs for `rows` live token rows (slots x Bk in a
    pass): rows x k assignments through one D x F matrix each, and that
    matrix of the `touched` experts read once; activations counted too."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = cfg["num_experts_per_tok"]
    touched = experts_touched(cfg, rows) if touched is None else touched
    flops = 2.0 * rows * k * d * f
    bytes_ = touched * d * f * _BYTES[cfg["serving"]["weight_dtype"]] \
        + rows * k * (d + f) * 2
    return flops, bytes_


def attention_cost(cfg: Dict, kv_tokens: float, rows_written: float,
                   slot_passes: float) -> Tuple[float, float]:
    """(flops, bytes) the decode-attention calls of some passes need over
    ALL layers: `kv_tokens` KV tokens read and `rows_written` K rows
    written (both summed over the layers, as the unit's counters give
    them), `slot_passes` (slot, pass) pairs of Bk query positions each."""
    hkv, dh, h = cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_attention_heads"]
    b = _BYTES[cfg["serving"]["kv_cache_dtype"]]
    bk = procedure(cfg)["gen_block"]
    row = 2 * hkv * dh * b  # K and V of one token in one layer
    flops = 4.0 * h * dh * bk * kv_tokens
    bytes_ = row * (kv_tokens + rows_written) \
        + slot_passes * cfg["num_hidden_layers"] * bk * 2 * h * dh * 2
    return flops, bytes_
