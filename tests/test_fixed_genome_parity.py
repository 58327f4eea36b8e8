"""Parity of the one-dispatch fixed-genome replay against the cost model.

``evaluate_fixed_genome`` batches every layer of a model into padded
``evaluate_rows`` dispatches (with a traced per-row hard-partition flag);
the reference is the plain per-layer ``evaluate_mapping`` jit with static
flags.  Cross-checked bit-for-bit across EVERY workload in ``workloads.py``
and both soft/hard-partition specs, plus the campaign's multi-model
``evaluate_fixed_genome_many`` against its per-model splits.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FULLFLEX, MODEL_ZOO, PARTFLEX, evaluate_fixed_genome,
                        evaluate_fixed_genome_many, evaluate_mapping,
                        get_model, make_variant)
from repro.core.mapspace import mapspace_for
from repro.core.workloads import group_table

# raw genome: baseline-ish tiles + arbitrary (mod-table) O/P/S/R indices
GENOME = np.asarray([64, 16, 3, 3, 3, 3, 5, 7, 11, 0], np.int32)
# the legacy 9-gene form must keep replaying identically (clip zero-pads R)
GENOME_V4 = GENOME[:9]

SPECS = [make_variant("1111", FULLFLEX), make_variant("1111", PARTFLEX)]


@pytest.mark.parametrize("model", sorted(MODEL_ZOO))
def test_batched_replay_matches_per_layer_cost_model(model):
    layers = get_model(model)
    for spec in SPECS:
        res = evaluate_fixed_genome(layers, spec, GENOME)
        assert len(res.per_layer) == len(layers)
        for layer, r in zip(layers, res.per_layer):
            space = mapspace_for(layer, spec)
            g = space.clip(GENOME[None, :])
            assert np.array_equal(g, space.clip(GENOME_V4[None, :]))
            t, o, p, s, rbits = space.decode_batch(g)
            # native-pinned R replays through the pre-R program, so the
            # bit-exact reference is the legacy (repr_bits=None) jit; a
            # grouped or ragged layer also passes its kind (plain and
            # depthwise layers pass none, the pre-kinds program)
            assert rbits[0] == 8 * spec.hw.bytes_per_elem
            gd, gl = group_table([layer], 1)
            ref = evaluate_mapping(
                jnp.asarray(space.dims), jnp.asarray(layer.stride),
                jnp.asarray(layer.depthwise), jnp.asarray(t[0]),
                jnp.asarray(o[0]), jnp.asarray(p[0]), jnp.asarray(s[0]),
                hw=spec.hw, hard_partition=space.hard_partition,
                grouped=jnp.asarray(True) if layer.grouped else None,
                groups=(gd[0], gl[0]) if layer.ragged else None)
            assert r.runtime == float(ref.runtime)
            assert r.energy == float(ref.energy)
            assert r.edp == float(ref.edp)
            assert r.util == float(ref.util)
            assert r.dram_elems == float(ref.dram_elems)
            assert r.feasible == bool(ref.feasible)
            assert r.mapping == space.decode(g[0])
        # model aggregate is the masked per-layer reduction
        assert res.runtime == float(sum(r.runtime for r in res.per_layer))
        assert res.energy == float(sum(r.energy for r in res.per_layer))


def test_many_model_replay_matches_per_model_calls():
    """The campaign replay (all models flattened into one chunked row list)
    must split back into exactly the per-model results."""
    spec = SPECS[0]
    names = sorted(MODEL_ZOO)
    many = evaluate_fixed_genome_many(
        [(get_model(m), spec, GENOME) for m in names])
    for name, combined in zip(names, many):
        solo = evaluate_fixed_genome(get_model(name), spec, GENOME)
        assert combined.runtime == solo.runtime
        assert combined.energy == solo.energy
        assert combined.edp == solo.edp
        for ra, rb in zip(combined.per_layer, solo.per_layer):
            assert ra.runtime == rb.runtime
            assert ra.feasible == rb.feasible
            assert ra.mapping == rb.mapping
