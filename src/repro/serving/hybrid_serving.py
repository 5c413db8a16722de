"""Hybrid serving tier: the paper's §2.2.1 deployment, end to end.

Request path:
  1. feature extraction (netsim) produced a feature vector per request;
  2. the SWITCH TIER — the fused IIsy table pipeline — classifies the
     whole batch at line rate and yields (class, confidence);
  3. confidence >= tau  -> answered at the switch (dropped / tagged /
     fast-pathed per use case);
  4. confidence <  tau  -> the low-confidence subset is *compacted* into a
     fixed-capacity buffer (a stable sort of the forwarded rows to the
     front) and only that buffer hits the BACKEND, the full-grown model
     (paper-faithful). This is the paper's back-end load
     reduction, in batch-size form: the expensive model runs on blocks
     of ``B`` rows of that buffer (``core.hybrid.backend_block``: 128
     where ``capacity`` is a multiple of it, else the whole buffer), only
     on those that hold forwarded rows, never on the full batch.
     ``HybridStats.backend_rows`` / ``capacity`` is the fill of the
     dispatch buffer, ``HybridStats.backend_blocks`` the blocks the
     backend ran.

Zero-sync single-dispatch path: switch classify + dispatch + backend +
combine are ONE jitted function, so a classify() is a
single device dispatch with no host round-trips in between. Telemetry
(fraction handled, backend occupancy — Figs 10-11's sweep quantities)
returns as device arrays wrapped in a lazy HybridStats: nothing blocks on
a float()/int() host sync unless the caller actually reads a statistic.

Backends that cannot be traced (e.g. they call into a foreign runtime)
are detected on the first classify and served by a two-phase fallback:
jitted switch+dispatch, host backend call, jitted combine — still one
host hop fewer than the pre-refactor path.

Threshold: tau lives on the device as an ``f32[]`` scalar, made once
when the threshold is set (``HybridServer.threshold``) and passed to the
step as a traced argument, so a call moves only its rows to the device
and a new tau never recompiles. The host counter ``threshold_uploads``
counts the scalars made.

Backend weights: the step takes the backend as an argument, a
``jax.tree_util.Partial``. One that carries its arrays as the Partial's
leaves, such as ``Partial(predict_tree_ensemble, ens)``, has them put on
the device once, at construction, inside the host span
``hybrid.backend_put``, and traced: the compiled step holds none of their
bytes. A plain function is wrapped as a Partial with no leaves, so the
arrays it closes over lower into the step as constants, as before.

Tracing: every classify opens host spans (``repro.obs.span``) that share
the call's id, ``hybrid.h2d`` around the transfer of the rows to the
device and ``hybrid.dispatch`` around the step (on the
two-phase path around the whole call, with ``hybrid.backend_host``
inside it around the host backend). Inside the step, ``jax.named_scope``
marks ``switch`` (fused classify), ``dispatch`` (threshold, sort,
gather), ``backend`` and ``combine``; TPU op events carry no scope, so
``HybridServer.step_scopes`` maps the compiled step's instructions to
them. The host counter ``calls`` numbers the requests and gives the
spans their id; it adds no device sync.

Wide rows: where the switch parses a few columns of a wider request (the
finance deployment's 5 of 130 features), ``switch_features`` names them.
The step then classifies those columns inside ``switch`` and ``dispatch``
gathers the full-width forwarded rows into the backend buffer, so one
fused step serves a switch and a backend that read different columns.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.core.artifact import TableArtifact, finalize_artifact
from repro.core.hybrid import (backend_block, backend_over_blocks,
                               combine, dispatch, switch_columns)
from repro.kernels.ops import fused_classify
from repro.kernels.tuning import (DEFAULT_TILES, TileConfig, autotune_tiles,
                                  resolve_use_pallas)
from repro.obs import op_scopes, span


class HybridStats:
    """Per-batch telemetry holding device arrays; converts lazily.

    Reading .fraction_handled / .backend_rows is the only point that
    blocks on the device — constructing or returning HybridStats never
    does, which keeps classify() fully asynchronous.

    ``backend_rows`` is the dispatch layer's counter: the rows of the
    call that reached the backend, at most ``capacity``.
    ``backend_blocks`` is derived from it: the blocks of ``B`` rows
    (``core.hybrid.backend_block``) the fused step ran the backend on,
    ``ceil(backend_rows / B)``, at most ``capacity // B``. (The
    two-phase path still hands the backend the whole buffer.)
    """

    __slots__ = ("_fraction_handled", "_backend_rows", "capacity")

    def __init__(self, fraction_handled, backend_rows, capacity: int):
        self._fraction_handled = fraction_handled
        self._backend_rows = backend_rows
        self.capacity = capacity

    @property
    def fraction_handled(self) -> float:
        return float(self._fraction_handled)

    @property
    def backend_rows(self) -> int:
        return int(self._backend_rows)

    @property
    def backend_blocks(self) -> int:
        return -(-self.backend_rows // backend_block(self.capacity))

    def as_arrays(self):
        """(fraction_handled, backend_rows) as device arrays — no sync."""
        return self._fraction_handled, self._backend_rows

    def __repr__(self):
        return (f"HybridStats(fraction_handled={self.fraction_handled:.3f}, "
                f"backend_rows={self.backend_rows}, "
                f"backend_blocks={self.backend_blocks}, "
                f"capacity={self.capacity})")


class HybridServer:
    # Hot-path auditor contract (repro.analysis.hotpath): the batch step
    # is audited for zero-sync and dtype layout with an empty donation
    # set — it carries no state, and its outputs (pred (N,) i32 + scalar
    # telemetry) could alias no (N, F) f32 input.
    AUDIT_CONTRACTS = (
        {"attr": "_step", "donate": (), "probe": "batch",
         "collectives": {}},
    )

    def __init__(self, artifact: TableArtifact, backend_fn: Callable,
                 *, threshold: float = 0.7, capacity: int = 256,
                 use_pallas: Optional[bool] = None, autotune: bool = False,
                 tiles: Optional[TileConfig] = None,
                 fuse: Optional[bool] = None,
                 switch_features: Optional[Sequence[int]] = None):
        """backend_fn: (rows (B, F)) -> class predictions (B,), run by the
        fused step on each block of ``B`` rows of the (capacity, F)
        dispatch buffer that holds forwarded rows, and on none when no
        row is forwarded (``core.hybrid.backend_over_blocks``; ``B`` is
        128 where ``capacity`` is a multiple of it, else ``capacity``).
        The two-phase path hands it the whole (capacity, F) buffer. A
        ``jax.tree_util.Partial`` over a model has its leaves put on the
        device here and passed to the step as arguments.

        switch_features: the column indices of a request row that the
        switch parses, in the artifact's feature order (static: they are
        baked into the step). The artifact then classifies
        ``x[:, switch_features]`` and ``backend_fn`` receives the whole
        rows. None (the default): the switch reads every
        column, and the rows are the artifact's width.

        use_pallas: None (the default) resolves by platform — the Pallas
        kernels on TPU, the bit-identical XLA references elsewhere
        (``kernels.tuning.resolve_use_pallas``); the resolved bool is kept
        as ``self.use_pallas``. Pass False for a reference server on a
        TPU host, True to run the kernels in interpret mode on CPU.

        autotune=True sweeps kernel tile sizes once for this artifact shape
        (cached per shape+backend; only meaningful — and only run — when
        the kernels are on, since the XLA reference path ignores tile
        configs).

        fuse: None probes on the first classify whether backend_fn traces
        into the single-dispatch step; False forces the two-phase path.
        Backends that *appear* traceable but read mutable side-channels
        (per-batch state on the function object) MUST pass fuse=False —
        tracing would bake the first batch's state in as a constant.
        """
        self.artifact = finalize_artifact(artifact)
        # capacity and backend_fn are baked into the jitted step: frozen.
        # threshold is a *traced* argument, so it stays tunable per call
        # (sweeping tau never recompiles); the setter keeps it on the
        # device as the f32[] scalar every classify passes to the step.
        self._backend_fn = backend_fn
        # the step's backend argument: a Partial's leaves (the forest of
        # ``Partial(predict_tree_ensemble, ens)``) go to the device once
        # and are traced; a plain function is a Partial with no leaves
        backend = (backend_fn if isinstance(backend_fn, Partial)
                   else Partial(backend_fn))
        if jax.tree.leaves(backend):
            with span("hybrid.backend_put"):
                backend = jax.block_until_ready(jax.device_put(backend))
        self._backend = backend
        self._capacity = capacity
        self.threshold_uploads = 0              # device scalars made for tau
        self.threshold = threshold
        if switch_features is not None:
            switch_features = tuple(int(c) for c in switch_features)
            if len(switch_features) != self.artifact.n_features:
                raise ValueError(
                    f"switch_features names {len(switch_features)} columns; "
                    f"the artifact reads {self.artifact.n_features}")
        self.use_pallas = use_pallas = resolve_use_pallas(use_pallas)
        # tiles only steer the Pallas kernels; sweeping them for the XLA
        # reference path would be pure init latency
        self.tiles = tiles or (autotune_tiles(self.artifact)
                               if autotune and use_pallas else DEFAULT_TILES)
        self._fused_ok = fuse                   # None = not yet probed
        self.calls = 0                          # classify calls; span ids

        def switch_only(art, x, threshold):
            with jax.named_scope("switch"):
                sw_pred, conf = fused_classify(
                    art, switch_columns(x, switch_features),
                    use_pallas=use_pallas, tiles=self.tiles)
            with jax.named_scope("dispatch"):
                fwd = conf < threshold
                buf, idx, valid = dispatch(x, fwd, capacity)
                frac = 1.0 - jnp.mean(fwd.astype(jnp.float32))
                rows = jnp.sum(valid.astype(jnp.int32))
            return sw_pred, buf, idx, valid, frac, rows

        def step(art, x, threshold, backend=backend):
            sw_pred, buf, idx, valid, frac, rows = switch_only(art, x,
                                                               threshold)
            with jax.named_scope("backend"):
                be_pred = backend_over_blocks(backend, buf, rows)
            with jax.named_scope("combine"):
                pred = combine(sw_pred, be_pred, idx, valid)
            return pred, frac, rows

        self._step = jax.jit(step)
        self._switch_only = jax.jit(switch_only)
        self._combine = jax.jit(combine)

    @property
    def threshold(self) -> float:
        """tau: rows whose switch confidence falls below it go to the
        backend."""
        return self._threshold

    @threshold.setter
    def threshold(self, value: float):
        """Puts a new tau on the device, once: every classify passes that
        f32[] scalar to the step, which takes it as a traced argument."""
        if self.threshold_uploads and value == self._threshold:
            return
        self._threshold = value
        self._tau = jax.device_put(np.float32(value))
        self.threshold_uploads += 1

    @property
    def capacity(self) -> int:
        """Backend buffer size. Frozen: it fixes the compiled shapes —
        build a new server to change it."""
        return self._capacity

    @property
    def backend_fn(self):
        """Frozen: traced into the fused step at construction."""
        return self._backend_fn

    def classify(self, x):
        """x (N, F) -> (pred (N,), HybridStats). Fully async: nothing here
        blocks on the device; read the stats (or the preds) to sync."""
        call = self.calls
        self.calls += 1
        with span("hybrid.h2d", call=call):
            x = jnp.asarray(x, jnp.float32)
        tau = self._tau
        with span("hybrid.dispatch", call=call):
            if self._fused_ok is None:
                try:
                    pred, frac, rows = self._step(self.artifact, x, tau,
                                                  self._backend)
                    self._fused_ok = True
                    return pred, HybridStats(frac, rows, self.capacity)
                except (jax.errors.JAXTypeError, TypeError):
                    # backend_fn is not traceable; tracing failed before
                    # any execution
                    self._fused_ok = False
            if self._fused_ok:
                pred, frac, rows = self._step(self.artifact, x, tau,
                                              self._backend)
                return pred, HybridStats(frac, rows, self.capacity)
            # two-phase fallback: untraceable backend runs on host between
            # the jitted switch half and the jitted combine
            sw_pred, buf, idx, valid, frac, rows = self._switch_only(
                self.artifact, x, tau)
            with span("hybrid.backend_host", call=call):
                be_pred = jnp.asarray(self.backend_fn(buf))
            pred = self._combine(sw_pred, be_pred, idx, valid)
        return pred, HybridStats(frac, rows, self.capacity)

    def step_scopes(self, n_rows: int,
                    n_features: Optional[int] = None) -> dict:
        """{instruction name: scope} of the fused step compiled for
        ``n_rows`` rows of ``n_features`` columns (by default the
        artifact's; a server with ``switch_features`` needs the request
        width) (``repro.obs.op_scopes``): how a profiler trace's op events
        of ``jit_step`` split into ``switch``, ``dispatch``, ``backend``
        and ``combine``. Compiles (or loads) the step; call it outside a
        timed window."""
        x = jax.ShapeDtypeStruct(
            (n_rows, n_features or self.artifact.n_features), jnp.float32)
        compiled = self._step.lower(self.artifact, x, self._tau,
                                    self._backend).compile()
        return op_scopes(compiled.as_text())

    def update_tables(self, artifact: TableArtifact):
        """§4.4: retraining swaps table *contents*; nothing recompiles as
        long as shapes (the model constraints) are unchanged."""
        artifact = finalize_artifact(artifact)
        try:
            same = jax.tree.map(lambda a, b: a.shape == b.shape,
                                self.artifact, artifact)
            ok = all(jax.tree.leaves(same))
        except ValueError:                      # tree structure mismatch
            ok = False
        if not ok:
            raise ValueError("table shapes changed: constraints violated "
                             "(paper §4.4 requires fixed model constraints)")
        self.artifact = artifact
