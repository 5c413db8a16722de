"""Jaxpr hot-path auditor (rule section ``hotpath``).

Builds tiny instances of every serving tier — `HybridServer`,
`StreamingHybridServer` (per-window and chunked), and
`ShardedStreamingServer` — and statically proves, on their *actual*
jitted closures, the contracts the code and DESIGN.md §5/§6/§8 claim:

* **donation** — every leaf of every ``donate_argnums`` buffer in each
  server's ``AUDIT_CONTRACTS`` really aliases an output in the compiled
  HLO (``input_output_alias``). jax prunes unusable donations *silently*,
  so a refactor that breaks aliasing (e.g. changing a carry's dtype or
  dropping it from the outputs) shows up as a silent extra copy per
  window — this rule turns that into a CI failure.
* **zero-sync** — no host-callback / infeed / outfeed / device_put
  primitive anywhere in the step jaxprs: the serving loop never blocks
  on the host.
* **dtype layout** — the traced steps use only the DESIGN.md register
  layout (f32 registers/conf, i32/bool control); any f64 promotion or
  stray wide integer fails.
* **collectives** — the sharded steps contain *exactly* the promised
  collective census: the psum counts (one rank>=2 "readout" psum per
  step/chunk, DESIGN.md §6/§8) plus the partitioned-classify lane-slab
  merges (one rank>=2 reduce_scatter and two all_gathers per step,
  DESIGN.md §16) — no accidental extra merges, and no silent fallback
  to the replicated-classify layout (losing the scatter would change
  the census too).

Servers declare what to audit via ``AUDIT_CONTRACTS`` rows
(attr/donate/probe/collectives); the auditor owns *how* to check.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import jaxpr_utils as JU
from repro.analysis.registry import Finding, Rule, register

# Every dtype the serving jaxprs are allowed to touch (DESIGN.md §5/§8:
# f32 registers + conf_sum, i32 stats counters, bool masks).
ALLOWED_DTYPES = frozenset({"float32", "int32", "bool"})

# Small probe geometry: big enough to exercise every code path (scatter
# conflicts, dispatch, chunk scan), small enough to trace in ~seconds.
PROBE = dict(window=32, n_buckets=64, capacity=8, chunk_windows=4,
             threshold=0.7, seed=0)


def _traceable_backend(rows):
    """A backend the fused step can trace through (all-zeros answers)."""
    return jnp.zeros(rows.shape[0], jnp.int32)


@functools.lru_cache(maxsize=1)
def _probe_artifact():
    """Tiny finalized RF artifact over the FLOW_FEATURES readout layout
    (the streaming tiers' readout emits FLOW_FEATURES-wide rows, so the
    probe model must be trained on that many features)."""
    from repro.core.artifact import finalize_artifact
    from repro.core.mapping import map_tree_ensemble
    from repro.ml.trees import fit_random_forest
    from repro.netsim.stream import FLOW_FEATURES
    rng = np.random.RandomState(0)
    x = rng.rand(256, FLOW_FEATURES).astype(np.float32) * 1500.0
    y = (x[:, 0] > x[:, 1]).astype(np.int32)
    m = fit_random_forest(x, y, n_classes=2, n_trees=3, max_depth=3)
    return finalize_artifact(map_tree_ensemble(m, FLOW_FEATURES))


@functools.lru_cache(maxsize=1)
def _audit_targets():
    """(label, server, contract, args) rows for every audited step."""
    from repro.serving.hybrid_serving import HybridServer
    from repro.serving.shard_serving import ShardedStreamingServer
    from repro.serving.stream_serving import (StreamingHybridServer,
                                              probe_chunk, probe_window)
    art = _probe_artifact()
    p = PROBE
    servers = [
        ("HybridServer",
         HybridServer(art, _traceable_backend, capacity=p["capacity"])),
        ("StreamingHybridServer",
         StreamingHybridServer(art, _traceable_backend,
                               n_buckets=p["n_buckets"],
                               window=p["window"], capacity=p["capacity"])),
        ("StreamingHybridServer[chunked]",
         StreamingHybridServer(art, _traceable_backend,
                               n_buckets=p["n_buckets"], window=p["window"],
                               capacity=p["capacity"],
                               chunk_windows=p["chunk_windows"])),
        ("ShardedStreamingServer",
         ShardedStreamingServer(art, _traceable_backend, n_shards=1,
                                n_buckets=p["n_buckets"], window=p["window"],
                                capacity=p["capacity"],
                                chunk_windows=p["chunk_windows"])),
    ]
    if jax.device_count() >= 4:
        # the census contracts are mesh-shape-invariant; audit the real
        # 2D ('shard', 'data') layout whenever the host platform provides
        # the devices (CI's 4-host-device step)
        from repro.distributed.sharding import flow_shard_mesh
        servers.append(
            ("ShardedStreamingServer[2x2]",
             ShardedStreamingServer(art, _traceable_backend,
                                    mesh=flow_shard_mesh(2, 2),
                                    n_buckets=p["n_buckets"],
                                    window=p["window"],
                                    capacity=p["capacity"],
                                    chunk_windows=p["chunk_windows"])))
    w = probe_window(p["window"], p["n_buckets"], p["seed"])
    chunk = probe_chunk(p["window"], p["chunk_windows"], p["n_buckets"],
                        p["seed"])
    xbatch = jnp.asarray(
        np.random.RandomState(p["seed"])
        .rand(p["window"], art.edges.shape[0]).astype(np.float32))
    tau = jnp.float32(p["threshold"])

    targets = []
    for label, srv in servers:
        for contract in srv.AUDIT_CONTRACTS:
            attr = contract["attr"]
            if label.endswith("[chunked]") and contract["probe"] != "chunk":
                continue    # window steps already audited on the
                #             per-window instance; don't trace them twice
            if not hasattr(srv, attr):
                targets.append((f"{label}.{attr}", srv, contract, None))
                continue
            if contract["probe"] == "window":
                args = (srv.artifact, srv._state, srv._stats, w, tau)
            elif contract["probe"] == "chunk":
                if srv.chunk_windows is None:
                    continue            # per-window server: no chunk step
                args = (srv.artifact, srv._state, srv._stats, chunk, tau)
            elif contract["probe"] == "batch":
                args = (srv.artifact, xbatch, tau)
            else:
                raise ValueError(f"unknown probe {contract['probe']!r}")
            # _stream_switch takes (art, state, w, tau) — no stats carry
            if attr == "_stream_switch":
                args = (srv.artifact, srv._state, w, tau)
            targets.append((f"{label}.{attr}", srv, contract, args))
    return targets


@functools.lru_cache(maxsize=None)
def _traced(label: str):
    """(closed_jaxpr, compiled_text, contract, args) for one target —
    traced once, shared by all four rules."""
    for tlabel, srv, contract, args in _audit_targets():
        if tlabel == label:
            if args is None:
                return None
            fn = getattr(srv, contract["attr"])
            return (JU.closed_jaxpr(fn, *args), JU.compiled_text(fn, *args),
                    contract, args)
    raise KeyError(label)


def _target_labels() -> List[str]:
    return [label for label, _, _, _ in _audit_targets()]


# -- rules ------------------------------------------------------------------


def check_donation() -> List[Finding]:
    out: List[Finding] = []
    for label in _target_labels():
        traced = _traced(label)
        if traced is None:
            out.append(Finding(rule="hotpath-donation",
                               message=f"{label}: contracted step attribute "
                                       "is missing on the server"))
            continue
        _, text, contract, args = traced
        want = JU.count_donated_leaves(args, contract["donate"])
        got = JU.donation_alias_count(text)
        if got < want:
            out.append(Finding(
                rule="hotpath-donation",
                message=(f"{label}: only {got}/{want} donated buffer leaves "
                         "alias an output in the compiled HLO — jax "
                         "silently pruned the rest (an extra device copy "
                         "per step)")))
    return out


def check_zero_sync() -> List[Finding]:
    out: List[Finding] = []
    for label in _target_labels():
        traced = _traced(label)
        if traced is None:
            continue                     # donation rule already reports it
        jaxpr, _, _, _ = traced
        hits = JU.forbidden_primitives(jaxpr)
        if hits:
            out.append(Finding(
                rule="hotpath-zero-sync",
                message=(f"{label}: host-sync/transfer primitives in the "
                         f"serving step jaxpr: {sorted(set(hits))}")))
    return out


def check_dtypes() -> List[Finding]:
    out: List[Finding] = []
    for label in _target_labels():
        traced = _traced(label)
        if traced is None:
            continue
        jaxpr, _, _, _ = traced
        bad = sorted(JU.jaxpr_dtypes(jaxpr) - ALLOWED_DTYPES)
        if bad:
            out.append(Finding(
                rule="hotpath-dtype",
                message=(f"{label}: dtypes outside the DESIGN.md §5/§8 "
                         f"register layout {sorted(ALLOWED_DTYPES)}: {bad}")))
    return out


def _readout_psum_count(jaxpr) -> int:
    """psum equations whose outputs are rank >= 2 (the readout merges)."""
    n = 0
    for eqn in JU.iter_eqns(jaxpr):
        if JU._normalize(eqn.primitive.name) == "psum":
            if any(getattr(v.aval, "ndim", 0) >= 2 for v in eqn.outvars):
                n += 1
    return n


def _readout_scatter_count(jaxpr) -> int:
    """reduce_scatter equations whose outputs are rank >= 2 (the
    partitioned classify's lane-slab merge — jax lowers psum_scatter to
    the reduce_scatter primitive). Rank >= 2 distinguishes the (T, F)
    feature-row scatter from any scalar/vector reduction that might
    legitimately appear."""
    n = 0
    for eqn in JU.iter_eqns(jaxpr):
        if JU._normalize(eqn.primitive.name) == "reduce_scatter":
            if any(getattr(v.aval, "ndim", 0) >= 2 for v in eqn.outvars):
                n += 1
    return n


def check_collectives() -> List[Finding]:
    out: List[Finding] = []
    for label in _target_labels():
        traced = _traced(label)
        if traced is None:
            continue
        jaxpr, _, contract, _ = traced
        census = JU.collective_census(jaxpr)
        want = dict(contract.get("collectives", {}))
        if census != want:
            out.append(Finding(
                rule="hotpath-collectives",
                message=(f"{label}: collective census {census} != "
                         f"contracted {want}")))
        want_readout = contract.get("readout_psums")
        if want_readout is not None:
            got = _readout_psum_count(jaxpr)
            if got != want_readout:
                out.append(Finding(
                    rule="hotpath-collectives",
                    message=(f"{label}: {got} rank>=2 readout psums, "
                             f"contract promises exactly {want_readout} "
                             "(DESIGN.md §6/§8)")))
        want_scatter = contract.get("readout_scatters")
        if want_scatter is not None:
            got = _readout_scatter_count(jaxpr)
            if got != want_scatter:
                out.append(Finding(
                    rule="hotpath-collectives",
                    message=(f"{label}: {got} rank>=2 lane-slab "
                             f"reduce_scatters, contract promises exactly "
                             f"{want_scatter} (DESIGN.md §16)")))
    return out


# -- seeded-violation self-tests --------------------------------------------


def _selftest_donation() -> List[Finding]:
    """A step that drops its donated carry from the outputs must be
    caught: jax prunes the alias with no warning."""
    import warnings

    def bad_step(state, w):
        return jnp.sum(state * w)        # state (donated) cannot alias a scalar
    jitted = jax.jit(bad_step, donate_argnums=(0,))
    args = (jnp.zeros((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32))
    with warnings.catch_warnings():
        # the seeded violation legitimately trips jax's donation warning
        warnings.simplefilter("ignore")
        text = JU.compiled_text(jitted, *args)
    want = JU.count_donated_leaves(args, (0,))
    got = JU.donation_alias_count(text)
    if got < want:
        return [Finding(rule="hotpath-donation",
                        message=f"selftest: {got}/{want} leaves aliased")]
    return []


def _selftest_zero_sync() -> List[Finding]:
    def bad_step(x):
        return jax.pure_callback(lambda a: a,
                                 jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    jaxpr = JU.closed_jaxpr(jax.jit(bad_step), jnp.zeros(4, jnp.float32))
    hits = JU.forbidden_primitives(jaxpr)
    if hits:
        return [Finding(rule="hotpath-zero-sync",
                        message=f"selftest: {sorted(set(hits))}")]
    return []


def _selftest_dtypes() -> List[Finding]:
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", True)

        def bad_step(x):
            return jnp.cumsum(x.astype(jnp.float64))
        jaxpr = JU.closed_jaxpr(bad_step, jnp.zeros(4, jnp.float32))
    finally:
        jax.config.update("jax_enable_x64", prev)
    bad = sorted(JU.jaxpr_dtypes(jaxpr) - ALLOWED_DTYPES)
    if bad:
        return [Finding(rule="hotpath-dtype",
                        message=f"selftest: {bad}")]
    return []


def _selftest_collectives() -> List[Finding]:
    """Seeded census violations must be caught: extra psums, extra
    reduce_scatters (wrong count), and a rank-1 scatter masquerading as
    the rank>=2 lane-slab merge (wrong rank)."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    out: List[Finding] = []

    def chatty(x):
        return jax.lax.psum(jax.lax.psum(x, "shard"), "shard")
    fn = jax.jit(jax.shard_map(chatty, mesh=mesh, in_specs=(P(),),
                               out_specs=P()))
    jaxpr = JU.closed_jaxpr(fn, jnp.zeros((4, 4), jnp.float32))
    census = JU.collective_census(jaxpr)
    if census != {"psum": 1}:
        out.append(Finding(rule="hotpath-collectives",
                           message=f"selftest: census {census} != promised "
                                   "{'psum': 1}"))

    # wrong count: two lane-slab scatters where the contract promises one
    def double_scatter(x):
        s = jax.lax.psum_scatter(x, "shard", scatter_dimension=0, tiled=True)
        return jax.lax.psum_scatter(s, "shard", scatter_dimension=0,
                                    tiled=True)
    fn = jax.jit(jax.shard_map(double_scatter, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    jaxpr = JU.closed_jaxpr(fn, jnp.zeros((4, 4), jnp.float32))
    census = JU.collective_census(jaxpr)
    if census.get("reduce_scatter") != 1:
        out.append(Finding(
            rule="hotpath-collectives",
            message=(f"selftest: census {census} != promised "
                     "{'reduce_scatter': 1}")))

    # wrong rank: a rank-1 scatter is NOT the (T, F) lane-slab merge —
    # _readout_scatter_count must refuse to count it toward the contract
    def vector_scatter(x):
        return jax.lax.psum_scatter(x, "shard", scatter_dimension=0,
                                    tiled=True)
    fn = jax.jit(jax.shard_map(vector_scatter, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    jaxpr = JU.closed_jaxpr(fn, jnp.zeros((8,), jnp.float32))
    got = _readout_scatter_count(jaxpr)
    if got != 1:
        out.append(Finding(
            rule="hotpath-collectives",
            message=(f"selftest: {got} rank>=2 lane-slab reduce_scatters, "
                     "contract promises exactly 1")))
    return out


def register_rules() -> None:
    register(Rule(name="hotpath-donation", section="hotpath",
                  doc="every contracted donate_argnums leaf aliases an "
                      "output in the compiled HLO (no silent copy)",
                  check=check_donation, selftest=_selftest_donation))
    register(Rule(name="hotpath-zero-sync", section="hotpath",
                  doc="no host callback / infeed / outfeed / device_put "
                      "primitives inside the jitted serving steps",
                  check=check_zero_sync, selftest=_selftest_zero_sync))
    register(Rule(name="hotpath-dtype", section="hotpath",
                  doc="serving-step jaxprs use only the f32/i32/bool "
                      "register layout (no f64 promotion)",
                  check=check_dtypes, selftest=_selftest_dtypes))
    register(Rule(name="hotpath-collectives", section="hotpath",
                  doc="sharded steps carry exactly the contracted "
                      "collective census (one readout psum and one "
                      "lane-slab reduce_scatter per step/chunk)",
                  check=check_collectives, selftest=_selftest_collectives))
