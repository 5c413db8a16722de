"""Tile-size selection for the fused lookup kernels.

The fused kernels take three tile knobs:

  tile_n        batch rows per grid step (VMEM tile height)
  edge_chunk    edges compared per sweep step in the range match
  dtable_chunk  decision entries compared per TCAM step

The best settings depend on the artifact shape (F, U, T/M, S) and the
backend (MXU tiles on TPU vs the interpret-mode grid overhead on CPU), so
``autotune_tiles`` times a small candidate sweep on synthetic data and
caches the winner per (artifact shape, backend). Serving calls it once at
server init (opt-in); everything else uses ``DEFAULT_TILES``.

``resolve_interpret`` is the backend auto-detect shared by every raw kernel
entry point: Pallas compiled on TPU, interpreter elsewhere — so direct
callers never run the interpreter on a real accelerator by accident.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TileConfig:
    tile_n: int = 128
    edge_chunk: int = 32
    dtable_chunk: int = 512
    select: str = "auto"     # decision-select strategy: matmul|compare|auto
    impl: str = "fused"      # kernel realization: fused|loop|ref — the
                             # autotune sweep includes the per-feature-loop
                             # kernel and the XLA gather reference as
                             # candidates, so shapes where the fused
                             # single-matmul loses (narrow/deep artifacts;
                             # BENCH_kernels.json rf_narrow) tune to the
                             # faster strategy instead of a regression


DEFAULT_TILES = TileConfig()

# Precision of every f32 matmul inside the kernels. Their operands are
# integer payloads riding as f32 (one-hots against table codes, packet
# lengths), and the results must be exact (DESIGN.md §2). The MXU's
# default f32 precision is a single bf16 pass, which rounds any value
# past 8 significant bits (a 1,001-byte packet, a decision key above
# 256); HIGHEST splits each operand into three bf16 parts, which covers
# the whole 24-bit f32 significand.
EXACT_F32 = jax.lax.Precision.HIGHEST

# Pallas-on-TPU sublane granularity for f32 (pallas_guide: min tile is
# 8 x 128) — the floor any clamped batch tile must respect.
MIN_TILE_N = 8

_TILE_CACHE: dict = {}


def padded_rows(n: int, tile: int) -> int:
    """Rows a tile-granular kernel actually processes for an n-row batch
    (``_pad_batch`` pads up to the next tile multiple)."""
    return -(-n // tile) * tile


def shard_tiles(tiles: TileConfig, batch: int) -> TileConfig:
    """Clamp ``tile_n`` to a partitioned per-device batch.

    The sharded classify hands each device a slab of ~K*W/D rows
    (DESIGN.md §16); with the full-width ``tile_n`` the kernel grid
    would pad that slab back up toward the unpartitioned batch and
    erase the per-device work reduction. Clamping to the slab (rounded
    up to the 8-row sublane floor) keeps padded work at
    ceil(slab/8)*8 — within one sublane of the ideal ceil(K*W/D). Only
    the fused realization tiles the batch; 'loop'/'ref' pass through.
    """
    if tiles.impl != "fused" or batch >= tiles.tile_n:
        return tiles
    return dataclasses.replace(
        tiles, tile_n=max(MIN_TILE_N, padded_rows(batch, MIN_TILE_N)))


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> interpreter off on TPU, on everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """None -> the Pallas kernels on TPU, the XLA references elsewhere.

    The one platform rule every kernel wrapper and server applies, so a
    server built without an explicit choice runs the kernels on the chip
    and the exact references on CPU."""
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def clear_tile_cache() -> None:
    _TILE_CACHE.clear()


def _artifact_key(art) -> tuple:
    if art.ftable is not None:
        return ("tree", art.agg, tuple(art.ftable.shape),
                tuple(art.dtable_class.shape))
    return ("classical", art.agg, tuple(art.vtable.q.shape))


def measure_min(fn, reps: int, warmup: int = 1) -> float:
    """min-over-reps wall time of ``fn()`` (which must block until the
    work is done). Warmup runs absorb compilation / first-trace cost;
    the minimum is robust to host load spikes — the measurement
    discipline every autotuner here shares."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def sweep_best(candidates, time_one, *, default, verbose: bool = False,
               label: str = "autotune") -> tuple:
    """Time each candidate, return (best, timings dict).

    ``default`` is ALWAYS timed (appended when missing from
    ``candidates``) and the winner is the measured argmin over a set
    containing it — so by construction the sweep can never select a
    config that regresses versus the default on the tuned shape. A
    non-default candidate whose ``time_one`` raises is skipped
    (unsupported config). The default is timed first and its failure
    propagates: it is what runs when nothing else wins, so a compile
    error there (a kernel the chip's compiler refused) must surface,
    not hide behind an untimed "winner".
    """
    timings = {}
    for cand in [default] + [c for c in candidates if c != default]:
        try:
            timings[cand] = time_one(cand)
        except Exception:  # noqa: BLE001 — candidate probing: any raise
            #                (compile error, OOM, shape mismatch) just means
            #                "config unsupported" — except for the default
            if cand == default:
                raise
            continue
        if verbose:
            print(f"{label} {cand} -> {timings[cand] * 1e3:.3f} ms")
    best = min(timings, key=timings.get)    # ties keep the default
    return best, timings


def _time_config(art, x, tiles: TileConfig, reps: int) -> float:
    from repro.kernels import ops as _ops

    @functools.partial(jax.jit, static_argnames=("tiles",))
    def run(art, x, tiles):
        return _ops.fused_classify(art, x, use_pallas=True, tiles=tiles)[0]

    return measure_min(lambda: run(art, x, tiles).block_until_ready(), reps)


def candidate_tiles(batch: int) -> list:
    """Small sweep: grid granularity × chunking × select strategy, plus
    the non-fused realizations (the per-feature-loop kernel and the XLA
    gather reference). Without them the tuner could only pick the least-
    bad *fused* config — on shapes where the fused single-matmul loses
    outright (BENCH_kernels.json: rf_narrow at 0.866x) that is a tuned
    regression; with them the loser falls back to the faster strategy."""
    cands = []
    for tile_n in (128, 512):
        if tile_n > batch:
            continue
        for dtable_chunk in (256, 1024):
            for select in ("matmul", "compare"):
                cands.append(TileConfig(tile_n=tile_n, edge_chunk=32,
                                        dtable_chunk=dtable_chunk,
                                        select=select))
    if not cands:       # batch below every tile: still time default fused
        cands.append(DEFAULT_TILES)
    cands.append(TileConfig(impl="loop"))   # skipped where unsupported
    cands.append(TileConfig(impl="ref"))
    return cands


def autotune_tiles(art, *, batch: int = 2048, reps: int = 2,
                   candidates=None, seed: int = 0,
                   verbose: bool = False) -> TileConfig:
    """Pick the fastest TileConfig for this artifact shape on this backend.

    Cached per (artifact shape, backend); the sweep runs on synthetic rows
    drawn around the edge range so the compare sweeps see realistic bins.
    """
    key = (_artifact_key(art), jax.default_backend(), batch)
    hit = _TILE_CACHE.get(key)
    if hit is not None:
        return hit
    edges = jnp.where(jnp.isfinite(art.edges), art.edges, 0.0)
    lo, hi = float(edges.min()), float(edges.max())
    span = max(hi - lo, 1.0)
    x = jax.random.uniform(jax.random.PRNGKey(seed),
                           (batch, art.n_features), jnp.float32,
                           lo - 0.1 * span, hi + 0.1 * span)
    best, _ = sweep_best(candidates or candidate_tiles(batch),
                         lambda tiles: _time_config(art, x, tiles, reps),
                         default=DEFAULT_TILES, verbose=verbose,
                         label="autotune")
    _TILE_CACHE[key] = best
    return best
