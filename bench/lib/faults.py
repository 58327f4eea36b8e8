"""Faults planted under the timed path, to read what ``correct`` makes of
them: ``bench/tests/test_correct.py`` plants each on the CPU, and
``bench/tools/calibrate.py --fault`` on the chip, for the upper readings of
the limits.

- ``state_unchanged``: the GA step returns its population unchanged.
- ``half_generations``: the GA runs half of its generations.
- ``half_batch``: half of each engine chunk is left out and its rows take
  the other half's answers.
- ``answer_altered``: one answer per chunk is altered where it is produced.

No cell spans chips, so the exchange between chips has no fault here.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_generations", "half_batch",
          "answer_altered")


def _loop_faults(name, real):
    """A ``jax.lax.fori_loop`` with the fault ``name`` planted; the GA
    program is the only loop of the campaign path."""
    if name == "state_unchanged":
        def loop(lo, hi, body, carry):
            return real(lo, hi,
                        lambda i, c: (c[0],) + tuple(body(i, c)[1:]), carry)
    else:
        def loop(lo, hi, body, carry):
            return real(lo, lo + (hi - lo) // 2, body, carry)
    return loop


def _collect_faults(name, real):
    if name == "half_batch":
        def collect(n_rows, gens, outputs):
            out = real(n_rows, gens, outputs)
            h = (len(out) + 1) // 2
            return out[:h] + out[:len(out) - h]
    else:
        def collect(n_rows, gens, outputs):
            out = real(n_rows, gens, outputs)
            out[0] = out[0]._replace(runtime=out[0].runtime * (1 + 1e-3))
            return out
    return collect


@contextlib.contextmanager
def planted(name: str):
    """Plant fault ``name`` for the duration of the block; the GA program's
    compiled copies are dropped on entry and on exit, so the fault is
    traced in and out."""
    import jax

    from repro.core import engine
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    if name in ("half_batch", "answer_altered"):
        owner, attr = engine, "_collect_chunk"
        patch = _collect_faults(name, engine._collect_chunk)
    else:
        owner, attr = jax.lax, "fori_loop"
        patch = _loop_faults(name, jax.lax.fori_loop)
    real = getattr(owner, attr)
    setattr(owner, attr, patch)
    engine._ga_program.clear_cache()
    try:
        yield
    finally:
        setattr(owner, attr, real)
        engine._ga_program.clear_cache()
