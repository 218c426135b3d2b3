"""The one way a Pallas kernel runs off a TPU: a test says so.

The kernels in seldon_tpu/ops never choose interpret mode themselves —
on a CPU they raise. Tests wrap the call (or the jit trace that contains
it) in :func:`pallas_interpret`, which passes ``interpret=True`` to every
``pl.pallas_call`` made inside the block (the generic interpreter: an
order of magnitude faster on CPU than ``pltpu.force_tpu_interpret_mode``,
which chip_smoke.py's rehearsal uses for its closer TPU semantics)."""

import contextlib
from unittest import mock

from jax.experimental import pallas as pl


@contextlib.contextmanager
def pallas_interpret():
    real = pl.pallas_call

    def interpreted(*args, **kwargs):
        kwargs["interpret"] = True
        return real(*args, **kwargs)

    with mock.patch.object(pl, "pallas_call", interpreted):
        yield
