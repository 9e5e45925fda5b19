"""Pallas admission kernel for the placement preference rounds.

One preference round of the capacity-admission step (see
`repro.cluster.placement`) is a *sequential contention loop*: container i
is admitted to its best region r iff fewer than ``remaining[r]`` wanters
of r precede it in container-index order. The XLA port ranks wanters
with a global ``lax.associative_scan`` over the full (N, R) one-hot
matrix — a multi-pass O(N R log N) tree that materializes rank
intermediates and defeats fusion on CPU (see the `placement_jax` module
docstring). This is exactly the shape Pallas exists for: the whole round
is a *single streaming pass* when per-region "wanters seen so far"
counters ride along the container axis.

Kernel layout (``admission_round``):

  - containers are laid out lane-dense as (rows, 128) tiles, padded to
    whole blocks of ``block_n`` containers (padding is never eligible);
    the grid runs over blocks, sequentially (``dimension_semantics=
    ("arbitrary",)``) so scratch carries across blocks;
  - per-region wanter counters are scalars in SMEM scratch — the only
    cross-block state; ``remaining`` comes in, and ``want_total`` goes
    out, through SMEM as well, read and written one region at a time;
  - per block: pick each container's best un-struck region from the
    epoch's integer preference table, rank each wanter as ``seen[r] +
    in-block prefix count``, admit iff rank <= ``remaining[r]`` (the
    round-start snapshot — identical to the NumPy kernel, which
    decrements per region *after* each region's cumsum), and strike
    denied choices into the bitmask;
  - the in-block prefix count is two small matmuls against constant
    triangular 0/1 matrices (within a 128-lane row, then across rows).
    Their operands are 0/1 or row totals <= 128, exact in bfloat16, and
    the f32 accumulation of at most ``block_n`` ones is exact, so the
    ranks are exact integers;
  - the per-round carry is two packed int32 vectors (dst, struck) — no
    (N, R) tensor survives the round.

The kernel sees integers only. The epoch's float64 net-saving table
never changes within the round loop, so `preference_ranks` turns it
once per epoch (in XLA, in f64) into an int32 rank per (region,
container): 0 for the best region, ties to the lower region index
(``np.argmax``'s first-max rule), and ``R`` for a region whose net
saving is not positive. The best un-struck region is then the one with
the smallest rank, and a container wants to move iff that rank is below
``R`` — the same decision as the f64 argmax over un-struck regions, bit
for bit, on every backend.

The denial/early-exit bookkeeping needs only the per-region wanter
totals: admitted(r) == min(want_total[r], remaining[r]) because
admission takes exactly the first ``remaining[r]`` wanters. The final
block publishes the SMEM counters as the (R,) ``want_total`` output.

The kernel is compiled by Mosaic on a TPU, where each round is one
custom call named ``admission_round`` in the compiled program and the
profiler's trace. Elsewhere it runs in interpret mode (``interpret=None``
resolves by the default backend), so CPU tests run the same kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 8192     # containers per grid step
_LANES = 128
_TILE = 8 * _LANES       # int32 (8, 128) tile: block granularity


def preference_ranks(net):
    """(N, R) float net-saving table -> (R, N) int32 preference ranks.

    ``rank[r, i]`` is the position of region r in container i's order
    of descending net saving (ties to the lower region index), or ``R``
    where ``net[i, r] <= 0``. The smallest rank among a container's
    un-struck regions names the region the f64 argmax would pick, and
    that rank is below ``R`` iff its net saving is positive.
    """
    R = net.shape[1]
    ranks = []
    for r in range(R):
        col = net[:, r]
        rank = jnp.zeros(col.shape, jnp.int32)
        for s in range(R):
            if s == r:
                continue
            ahead = (net[:, s] >= col) if s < r else (net[:, s] > col)
            rank = rank + ahead.astype(jnp.int32)
        ranks.append(jnp.where(col > 0.0, rank, R))
    return jnp.stack(ranks)


def _round_kernel(pref_ref, assign_ref, elig_ref, dst_ref, struck_ref,
                  remaining_ref, dst_out_ref, struck_out_ref, want_out_ref,
                  seen_ref, *, R: int, S: int, NB: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        for r in range(R):
            seen_ref[r] = 0

    assign = assign_ref[...]                 # (S, 128) current region
    elig = elig_ref[...] > 0                 # dwell >= min_dwell
    dst = dst_ref[...]                       # -1 = still unplaced
    struck = struck_ref[...]                 # denied-region bitmask

    # best un-struck region = smallest preference rank (ranks of
    # regions with a positive net are distinct; struck ones rank R)
    best = jnp.zeros(assign.shape, jnp.int32)
    best_rank = jnp.full(assign.shape, R, jnp.int32)
    for r in range(R):
        rank = jnp.where(((struck >> r) & 1) > 0, R, pref_ref[r])
        m = rank < best_rank
        best = jnp.where(m, r, best)
        best_rank = jnp.where(m, rank, best_rank)
    want = elig & (dst < 0) & (best_rank < R) & (best != assign)

    # in-block inclusive prefix count in container order (row-major):
    # within-row prefix (x @ upper) plus the totals of earlier rows
    # (lower_strict @ x @ ones); see the module docstring for exactness
    bf16 = jnp.bfloat16
    li = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    upper = jnp.where(li <= lj, 1.0, 0.0).astype(bf16)
    ones = jnp.ones((_LANES, _LANES), bf16)
    si = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    sj = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    lower = jnp.where(si > sj, 1.0, 0.0).astype(bf16)

    def dot(a, b_):
        return jax.lax.dot(a, b_, preferred_element_type=jnp.float32)

    dst_new = dst
    for r in range(R):
        wants_r = want & (best == r)
        x = jnp.where(wants_r, 1.0, 0.0).astype(bf16)
        row_tot = dot(x, ones).astype(bf16)
        prefix = (dot(x, upper) + dot(lower, row_tot)).astype(jnp.int32)
        seen = seen_ref[r]
        dst_new = jnp.where(wants_r & (seen + prefix <= remaining_ref[r]),
                            r, dst_new)
        # the inclusive prefix at the block's last container is the
        # block's wanter count
        seen_ref[r] = seen + prefix[S - 1, _LANES - 1]
    dst_out_ref[...] = dst_new
    denied = want & (dst_new < 0)
    struck_out_ref[...] = jnp.where(denied, struck | (1 << best), struck)

    @pl.when(b == NB - 1)
    def _publish():
        for r in range(R):
            want_out_ref[r] = seen_ref[r]


def default_interpret() -> bool:
    """Interpret mode everywhere but a TPU (the kernel is Mosaic-only)."""
    return jax.default_backend() != "tpu"


def admission_round(pref, assign, eligible, dst, struck, remaining, *,
                    block_n: int = DEFAULT_BLOCK, interpret=None):
    """One capacity-admission preference round as a single streaming pass.

    Inputs: ``pref`` (R, N) i32 epoch preference ranks
    (`preference_ranks`); ``assign`` (N,) i32 current regions;
    ``eligible`` (N,) i32/bool dwell gate; ``dst`` (N,) i32 round carry
    (-1 = unplaced); ``struck`` (N,) i32 denied-region bitmask carry;
    ``remaining`` (R,) i32 round-start free slots. ``block_n`` is
    rounded up to whole (8, 128) int32 tiles.

    Returns ``(dst', struck', want_total)`` with ``want_total`` (R,) i32
    the number of containers that requested each region this round —
    enough for the caller to update ``remaining`` (admitted ==
    min(want_total, remaining)) and evaluate the NumPy kernel's
    early-exit rule without touching (N, R) state.
    """
    R, N = pref.shape
    if interpret is None:
        interpret = default_interpret()
    n_tiles = max(1, -(-N // _TILE))
    B = _TILE * min(max(1, -(-block_n // _TILE)), n_tiles)
    NB = -(-N // B)
    pad = NB * B - N
    S = B // _LANES

    def lay(v, fill):                        # (N,) -> (NB * S, 128)
        return jnp.pad(v.astype(jnp.int32), (0, pad),
                       constant_values=fill).reshape(-1, _LANES)

    pref_t = jnp.pad(pref, ((0, 0), (0, pad)),
                     constant_values=R).reshape(R, -1, _LANES)
    vec = pl.BlockSpec((S, _LANES), lambda b: (b, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_round_kernel, R=R, S=S, NB=NB)
    args = (pref_t, lay(assign, 0), lay(eligible, 0), lay(dst, -1),
            lay(struck, 0), remaining.astype(jnp.int32))
    # trace the kernel and its index maps with 32-bit defaults: callers
    # run under enable_x64, and Mosaic has no 64-bit types
    with jax.enable_x64(False):
        dst2, struck2, want = pl.pallas_call(
            kernel,
            grid=(NB,),
            in_specs=[pl.BlockSpec((R, S, _LANES), lambda b: (0, b, 0)),
                      vec, vec, vec, vec, smem],
            out_specs=[vec, vec, smem],
            out_shape=[
                jax.ShapeDtypeStruct((NB * S, _LANES), jnp.int32),  # dst'
                jax.ShapeDtypeStruct((NB * S, _LANES), jnp.int32),  # struck'
                jax.ShapeDtypeStruct((R,), jnp.int32),         # want_total
            ],
            scratch_shapes=[pltpu.SMEM((R,), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="admission_round",
        )(*args)
    return dst2.reshape(-1)[:N], struck2.reshape(-1)[:N], want
