"""Shared GA randomness and operators for the MSE engine and its reference.

The golden-parity contract between the one-program engine
(``repro.core.engine``) and the per-layer reference GA the tests hold it to
(tests/_reference_ga.py) rests on two rules enforced by this module:

  1. **One random stream per (layer, spec) row.**  All data-independent
     randomness of a GA run — parent-selection ranks, crossover masks and
     permutations, mutation masks/steps/divisor snaps — is drawn up front by
     :func:`draw_run` from a single ``numpy`` Generator, in one fixed call
     order.  Both GAs call the same functions with the same seed, so they
     consume bit-identical draws no matter how the generations are executed.
     A row's draws come in two stages: the stream stage
     (:func:`draw_stream`, :func:`draw_run_stream`) makes every Generator
     call and depends on the map space only through :func:`r_open`; the
     row stage (:func:`row_draws`, :func:`run_draws`) applies the space.
     ``initial_population`` and ``draw_run`` are the two stages composed,
     and the engine draws each (seed, R-open) stream once per call and
     runs the row stage for every row that reads it.

  2. **One operator formula, two array backends.**  The apply functions below
     (`apply_crossover`, `apply_mutation`, `clip_genomes`) are written against
     the array-API subset shared by ``numpy`` and ``jax.numpy`` and take the
     backend as the ``xp`` argument.  Genomes are integers (exact in both
     backends) and the only floating-point arithmetic — the geometric tile
     step ``round(tile * step)`` — is forced to float32 on both sides, so the
     host loop and the jitted device loop produce identical genomes.

Rank-based parent selection is expressed as draws of *sorted positions* from
the fixed rank distribution (the probability of picking the j-th best genome
depends only on j), which makes the draw data-independent; engines turn a
position into a genome index via their own stable argsort.
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional, Tuple

import numpy as np

from .workloads import NUM_DIMS

GENOME_LEN = 10
N_IDX = 4  # index genes: order / parallel-pair / shape / representation


class GenDraws(NamedTuple):
    """All randomness for a GA run (or one generation when sliced with
    :func:`gen_slice`).  Leading axis of every field is the generation."""

    ranks: np.ndarray       # (G, Pc)     i32  rank-selection sorted positions
    perm: np.ndarray        # (G, Pc)     i32  crossover mate permutation
    cross_mask: np.ndarray  # (G, Pc, 10) bool per-gene swap mask
    cross_do: np.ndarray    # (G, Pc)     bool whether a child crosses at all
    m_tile: np.ndarray      # (G, Pc, 6)  bool tile-gene mutation mask
    step: np.ndarray        # (G, Pc, 6)  f32  geometric tile step factor
    snap: np.ndarray        # (G, Pc, 6)  bool snap-to-divisor mask
    dv: np.ndarray          # (G, Pc, 6)  i32  divisor value snapped to
    m_idx: np.ndarray       # (G, Pc, 4)  bool index-gene mutation mask
    walk: np.ndarray        # (G, Pc, 4)  bool +-1 walk (vs resample)
    stepdir: np.ndarray     # (G, Pc, 4)  i32  walk direction (+-1)
    sampled: np.ndarray     # (G, Pc, 4)  i32  resample target index


def gen_slice(draws: GenDraws, g: int) -> GenDraws:
    """The g-th generation's draws (drops the leading axis)."""
    return GenDraws(*(f[g] for f in draws))


# (dtype, trailing shape, inert value) of each GenDraws field: the inert
# values move no gene (no mask set, step 1.0, divisor 1, walk +1)
_LAYOUT = GenDraws(
    ranks=(np.int32, (), 0),
    perm=(np.int32, (), 0),
    cross_mask=(np.bool_, (GENOME_LEN,), False),
    cross_do=(np.bool_, (), False),
    m_tile=(np.bool_, (NUM_DIMS,), False),
    step=(np.float32, (NUM_DIMS,), 1.0),
    snap=(np.bool_, (NUM_DIMS,), False),
    dv=(np.int32, (NUM_DIMS,), 1),
    m_idx=(np.bool_, (N_IDX,), False),
    walk=(np.bool_, (N_IDX,), False),
    stepdir=(np.int32, (N_IDX,), 1),
    sampled=(np.int32, (N_IDX,), 0),
)


def _unwritten_draws(shape) -> GenDraws:
    return GenDraws(*(np.empty(shape + tail, dtype)
                      for dtype, tail, _ in _LAYOUT))


def empty_draw_stack(gens_pad: int, n_rows: int, n_children: int,
                     gens: int = 0, live: int = 0) -> GenDraws:
    """Draw arrays for a padded engine chunk, inert everywhere except
    ``[:gens, :live]``, which is left unwritten for the live rows to fill
    (:func:`run_draws` writes every element of its ``out``).  Rows past the
    true row count and generations past the fori_loop bound are never
    executed, so their contents only need shape-stable placeholders."""
    stack = _unwritten_draws((gens_pad, n_rows, n_children))
    for field, (_, _, inert) in zip(stack, _LAYOUT):
        field[gens:] = inert
        field[:gens, live:] = inert
    return stack


@lru_cache(maxsize=4096)
def divisors(n: int) -> np.ndarray:
    n = int(n)
    return np.asarray([d for d in range(1, n + 1) if n % d == 0], np.int32)


def n_elite(cfg) -> int:
    return max(1, int(cfg.elite_frac * cfg.population))


@lru_cache(maxsize=256)
def rank_probs(population: int) -> np.ndarray:
    """P(select the genome at sorted position j) = (P - j) / sum."""
    p = population - np.arange(population, dtype=np.float64)
    return p / p.sum()


@lru_cache(maxsize=256)
def _rank_cdf(population: int) -> np.ndarray:
    return np.cumsum(rank_probs(population))


# Column layout of the one bulk uniform slab a draw_run consumes (legacy
# T/O/P/S portion — identical to the 9-gene v4 stream):
#   0      parent-rank u        1:10   cross_mask     10     cross_do
#   11:17  m_tile               17:23  snap           23:29  divisor pick
#   29:32  m_idx                32:35  walk           35:38  resample
_U_COLS = 38

# R-axis slab (drawn ONLY when the R table is open, i.e. len > 1):
#   0  cross_mask gene 9        1  m_idx R       2  walk R      3  resample R
_U_R_COLS = 4


def r_open(lens) -> bool:
    """Whether the representation table is open, from a space's
    ``table_lens()``: the one test that decides whether a row's Generator
    makes the R-axis draws, so with the seed it keys the row's stream."""
    return bool(lens[3] > 1)


class RunStream(NamedTuple):
    """What one Generator gives a :func:`draw_run` of ``gens`` generations
    of ``n`` children before any map space is applied: every row whose
    Generator has the same seed and R test (:func:`r_open`) reads the same
    stream.  The shared fields are final; the rest are the unmasked tests,
    and the uniforms (one contiguous ``(G, n)`` slab per gene) that
    :func:`run_draws` turns into a row's fields."""

    r_open: bool
    ranks: np.ndarray       # (G, n)     i32  final
    perm: np.ndarray        # (G, n)     i32  final
    cross_mask: np.ndarray  # (G, n, 10) bool final
    cross_do: np.ndarray    # (G, n)     bool final
    step: np.ndarray        # (G, n, 6)  f32  final
    walk: np.ndarray        # (G, n, 4)  bool final
    stepdir: np.ndarray     # (G, n, 4)  i32  final
    m_tile_u: np.ndarray    # (G, n, 6)  bool u < mutation_rate
    snap_u: np.ndarray      # (G, n, 6)  bool u < tile_divisor_bias
    m_idx_u: np.ndarray     # (G, n, 4)  bool u < mutation_rate
    dv_u: np.ndarray        # (6, G, n)  f64  divisor-pick uniforms
    sampled_u: np.ndarray   # (4, G, n)  f64  resample uniforms
    columns: dict           # (G, n) i32 dv / sampled columns by (field,
    #                         gene, dim or table length), kept by run_draws
    #                         for the stream's other rows


_SHARED_FIELDS = ("ranks", "perm", "cross_mask", "cross_do", "step", "walk",
                  "stepdir")


def draw_run_stream(rng: np.random.Generator, open_r: bool, cfg, gens: int,
                    n: int) -> RunStream:
    """The stream stage of :func:`draw_run`: every Generator call of a run
    in its fixed order, and all that follows from the draws alone.

    Four bulk calls (uniform slab, normal steps, mate permutations, walk
    directions), then the R-axis slab (two more calls) ONLY when the
    representation table is open: a pinned-R run consumes the
    byte-identical Generator stream of the v4 9-gene engine, which is what
    makes the R-pinned golden metrics reproduce bit-identically.  The inert
    fill (1.0 / +1) makes every R-gene predicate false (1.0 < 0.5, 1.0 <
    rate for rate <= 1)."""
    u = rng.random((gens, n, _U_COLS))
    normal = rng.normal(0.0, 0.7, (gens, n, NUM_DIMS))
    perm = rng.permuted(
        np.tile(np.arange(n, dtype=np.int32), (gens, 1)), axis=1)
    stepdir = (rng.integers(0, 2, (gens, n, 3), dtype=np.int32) * 2 - 1)
    if open_r:
        u_r = rng.random((gens, n, _U_R_COLS))
        stepdir_r = (rng.integers(0, 2, (gens, n, 1), dtype=np.int32) * 2 - 1)
    else:
        u_r = np.ones((gens, n, _U_R_COLS))
        stepdir_r = np.ones((gens, n, 1), np.int32)

    # rank-based parent selection via inverse CDF over sorted positions
    # (clamped: float cumsum can top out a hair below 1.0)
    ranks = np.minimum(
        np.searchsorted(_rank_cdf(cfg.population), u[:, :, 0],
                        side="right"),
        cfg.population - 1).astype(np.int32)
    return RunStream(
        r_open=open_r, ranks=ranks, perm=perm,
        cross_mask=np.concatenate([u[:, :, 1:10], u_r[:, :, 0:1]],
                                  axis=-1) < 0.5,
        cross_do=u[:, :, 10] < cfg.crossover_rate,
        step=np.exp(normal).astype(np.float32),
        walk=np.concatenate([u[:, :, 32:35], u_r[:, :, 2:3]], axis=-1) < 0.5,
        stepdir=np.concatenate([stepdir, stepdir_r], axis=-1),
        m_tile_u=u[:, :, 11:17] < cfg.mutation_rate,
        snap_u=u[:, :, 17:23] < cfg.tile_divisor_bias,
        m_idx_u=np.concatenate([u[:, :, 29:32], u_r[:, :, 1:2]],
                               axis=-1) < cfg.mutation_rate,
        dv_u=np.ascontiguousarray(np.moveaxis(u[:, :, 23:29], -1, 0)),
        sampled_u=np.concatenate([np.moveaxis(u[:, :, 35:38], -1, 0),
                                  u_r[None, :, :, 3]]),
        columns={})


def _column(stream: RunStream, field: str, j: int, n: int) -> np.ndarray:
    """``dv`` for gene ``j`` of a dim ``n`` (a divisor of ``n`` picked
    uniformly), or ``sampled`` for index gene ``j`` of a table of length
    ``n``, from the stream's uniforms; each made once per stream."""
    col = stream.columns.get((field, j, n))
    if col is None:
        if field == "dv":
            divs = divisors(n)
            col = divs[(stream.dv_u[j] * len(divs)).astype(np.int64)]
        else:
            col = (stream.sampled_u[j] * n).astype(np.int32)
        stream.columns[field, j, n] = col
    return col


def run_draws(stream: RunStream, space,
              out: Optional[GenDraws] = None) -> GenDraws:
    """The row stage of :func:`draw_run`: ``stream`` under one map space.
    Pinned axes (InFlex or unit dims) have their masks forced off, so the
    applied operators never move them; ``space`` supplies those
    constraints (``tile_lo``/``tile_hi``, ``dims``, ``table_lens()``).
    Writes into ``out`` (arrays of the stream's leading shape, e.g. views
    into an engine chunk's draw stack) when given.

    The masks are copied whole and their pinned genes cleared, and ``dv``
    and ``sampled`` are written gene by gene from columns the stream keeps
    (:func:`_column`): numpy broadcasting a per-gene vector over the last
    axis runs an inner loop of 4 or 6 elements per child, several times
    slower."""
    lens = np.asarray(space.table_lens(), np.int64)             # (4,)
    assert r_open(lens) == stream.r_open, "stream drawn for another R test"
    if out is None:
        out = _unwritten_draws(stream.ranks.shape)
    for name in _SHARED_FIELDS:
        getattr(out, name)[...] = getattr(stream, name)
    tile_open = space.tile_lo != space.tile_hi                  # (6,)
    out.m_tile[...] = stream.m_tile_u
    out.m_tile[..., ~tile_open] = False
    out.snap[...] = stream.snap_u
    out.snap[..., ~tile_open] = False
    out.m_idx[...] = stream.m_idx_u
    out.m_idx[..., lens <= 1] = False
    for d in range(NUM_DIMS):
        out.dv[..., d] = (_column(stream, "dv", d, int(space.dims[d]))
                          if tile_open[d] else 1)
    for j in range(N_IDX):
        out.sampled[..., j] = _column(stream, "sampled", j, int(lens[j]))
    return out


def draw_run(rng: np.random.Generator, space, cfg, gens: int,
             n: int) -> GenDraws:
    """Draw every random quantity for ``gens`` generations of ``n`` children:
    :func:`draw_run_stream` then :func:`run_draws`."""
    return run_draws(draw_run_stream(rng, r_open(space.table_lens()), cfg,
                                     gens, n), space)


# --------------------------------------------------------------------------
# Operator formulas — one implementation, numpy or jax.numpy via ``xp``.
# The draw fields must already be sliced to one generation (no leading G).
# --------------------------------------------------------------------------

def clip_genomes(g, tile_lo, tile_hi, table_lens, xp=np):
    """Project genomes back into the legal axis-constrained space.

    Works on any leading batch shape ``(..., 10)``; ``tile_lo``/``tile_hi``/
    ``table_lens`` broadcast against it (per-row bounds for the batched
    engine, flat vectors for a single-layer host loop).
    """
    tiles = xp.clip(g[..., 0:6], tile_lo, tile_hi)
    idx = xp.mod(g[..., 6:10], table_lens)
    return xp.concatenate([tiles, idx], axis=-1)


def apply_crossover(parents, d: GenDraws, xp=np):
    """Uniform crossover against a permuted set of mates (GAMMA-style)."""
    mates = xp.take_along_axis(parents, d.perm[..., None], axis=-2)
    return xp.where(d.cross_do[..., None] & d.cross_mask, mates, parents)


def apply_mutation(g, d: GenDraws, tile_lo, tile_hi, table_lens, xp=np):
    """Tile genes: geometric step or divisor snap; index genes: +-1 walk or
    resample.  float32 step arithmetic on both backends (parity)."""
    tiles = g[..., 0:6]
    stepped = xp.maximum(
        1.0, xp.round(tiles.astype(xp.float32) * d.step)).astype(xp.int32)
    newv = xp.where(d.snap, d.dv, stepped)
    tiles = xp.where(d.m_tile, newv, tiles)
    idx = g[..., 6:10]
    cand = xp.where(d.walk, idx + d.stepdir, d.sampled)
    idx = xp.where(d.m_idx, cand, idx)
    return clip_genomes(xp.concatenate([tiles, idx], axis=-1),
                        tile_lo, tile_hi, table_lens, xp)


def next_population(pop, order_idx, d: GenDraws, tile_lo, tile_hi,
                    table_lens, n_elite: int, xp=np):
    """One host-side breeding step: elites survive, children are bred
    from rank-selected parents (crossover -> clip -> mutate).

    ``d`` must be one generation's draws (already ``gen_slice``\\ d).  This is
    THE host-side generation step — the reference GA
    (tests/_reference_ga.py) and the measured-objective kernel tuner
    (``kernel_bridge.tune_kernel``) both call it, so a modeled and a
    measured GA walking the same draw stream breed bit-identical genomes
    whenever their objectives rank populations the same way."""
    elites = pop[order_idx[:n_elite]]
    parents = pop[order_idx[d.ranks]]          # rank-based selection
    children = apply_crossover(parents, d, xp)
    children = clip_genomes(children, tile_lo, tile_hi, table_lens, xp)
    children = apply_mutation(children, d, tile_lo, tile_hi, table_lens, xp)
    return xp.concatenate([elites, children], axis=0)


def single_generation_draws(rng: np.random.Generator, space, cfg,
                            n: int) -> GenDraws:
    """One generation of draws for ``n`` genomes (standalone operator use,
    e.g. ``_Operators`` in mapper.py); same stream layout as draw_run."""
    return gen_slice(draw_run(rng, space, cfg, 1, n), 0)


def sample_uniforms(rng: np.random.Generator, open_r: bool,
                    n: int) -> np.ndarray:
    """The ``(n, 10)`` uniforms behind ``n`` sampled genomes: one ``(n, 9)``
    draw, then the R column in a SEPARATE call made only when the R table
    is open (zeros when pinned), so a pinned-R space consumes the
    byte-identical Generator stream of the v4 9-gene sampler."""
    u = rng.random((n, 9))
    u_r = rng.random((n, 1)) if open_r else np.zeros((n, 1))
    return np.concatenate([u, u_r], axis=-1)


def population_from(space, u: np.ndarray) -> np.ndarray:
    """The starting population from its :func:`sample_uniforms`, with slot 0
    seeded by the accelerator's baseline fixed mapping (clipped to the
    layer) so the InFlex design point is always present."""
    pop = space.genomes_from(u)
    base = space.clip(np.concatenate([
        np.minimum(np.asarray(space.spec.tile.fixed_tile, np.int32),
                   space.dims),
        [0, 0, 0, 0]])[None, :])
    pop[0] = base[0]
    return pop


def initial_population(rng: np.random.Generator, space, cfg) -> np.ndarray:
    """Sample the starting population (:func:`population_from`) — both
    engines start from this exact population."""
    return population_from(space, sample_uniforms(
        rng, r_open(space.table_lens()), cfg.population))


class DrawStream(NamedTuple):
    """A row's whole Generator sequence: its initial population's uniforms,
    then its GA run's stream."""

    pop_u: np.ndarray       # (P, 10) f64
    run: RunStream


def draw_stream(seed: int, open_r: bool, cfg, gens: int,
                n: int) -> DrawStream:
    """The stream stage of a row seeded ``seed``: what
    :func:`initial_population` then :func:`draw_run` draw from
    ``default_rng(seed)``.  It depends on the map space only through
    :func:`r_open`, so rows with equal ``(seed, open_r)`` share it."""
    rng = np.random.default_rng(seed)
    pop_u = sample_uniforms(rng, open_r, cfg.population)
    return DrawStream(pop_u, draw_run_stream(rng, open_r, cfg, gens, n))


def row_draws(stream: DrawStream, space, out: Optional[GenDraws] = None
              ) -> Tuple[np.ndarray, GenDraws]:
    """The row stage: ``stream`` under one map space, as the row's initial
    population and GA draws (into ``out`` when given, see
    :func:`run_draws`)."""
    return (population_from(space, stream.pop_u),
            run_draws(stream.run, space, out))
