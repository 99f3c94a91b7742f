"""Request-loop façades over the multi-tenant slot-grid session subsystem.

Two layers live here:

``SlotGridService`` — the service-AGNOSTIC core.  Everything that made the
TCN streaming service churn-tolerant turns out to be independent of what a
"slot" holds: a fixed compiled slot grid, admission control + LRU/cost
eviction (sessions/scheduler), a host-side parking lot of packed slot
columns, power-of-two chunk padding buckets (compiled programs bounded by
log2(T_chunk)+1), and checkpoint/store spill/restore of the lot.  Concrete
services supply four state hooks — ``_pack``/``_unpack``/``_reset`` move
one slot's column between device and host, ``_session_cls`` carries the
per-session host record — plus optional lifecycle/persistence hooks.  The
TCN service parks O(receptive-field) ring pytrees; the LM service
(sessions/lm.py) parks KV-cache columns truncated to the live position; a
third modality would only write the hooks.

``StreamSessionService`` — the TCN streaming façade on top of it:

    open_session / push_audio / enroll_shots / poll / close

The hot path is *chunk-native*: ``push_audio`` takes ragged per-session
time chunks {sid: (t_i, C_in)}, pads them onto the compiled (S, T_chunk)
grid, and advances every pushed session through ``sessions/state.grid_scan``
— a ``lax.scan`` over time inside ONE jitted dispatch, so a tick costs one
host↔device round trip for S×T_chunk samples instead of S.  Ragged lengths
become per-step validity masks, so short chunks and absent sessions stay
bit-frozen.  A single (C_in,) sample is the T=1 special case and keeps the
historical per-sample result surface.

A parked session resumes bit-identically in any free slot because its
entire stream position is its packed pytree; with ``quantize=True``
parkings are nibble-packed (~8x smaller, still bit-identical).
``spill_parking``/``restore_parking`` persist the lot through
checkpoint/store so sessions survive restarts.

``fused=True`` (or REPRO_TCN_FUSED=1) swaps the chunk body for the fused
kernel fast path: BN and the log2 weight quantization are baked once at
construction (models/tcn.bake_stream_params), and each tick runs one
fused block op per TCN block (kernels/tcn_block.py) over the ring-buffer
taps instead of a per-sample ``lax.scan`` — same slot grid, same parking
lot, same bit-exact park/resume; only the executor changes.  On the
baked params the fused executor is bit-identical to ``grid_scan``
(tests/test_streaming_chunk.py); vs an UNFUSED service on the raw params
outputs are allclose only, because BN folding reassociates by one ULP.

Passing a ``mesh`` shards the slot grid over the mesh's ``data`` axis and
the tenant banks over ``model`` (sessions/state.grid_pspecs,
sessions/tenancy.bank_pspecs); on a 1-device mesh everything degenerates
to replicated and behaviour is unchanged.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import load_sessions, save_sessions
from repro.configs.runtime import RuntimeConfig
from repro.core.protonet import pn_logits_banked
from repro.obs.device import (
    decode_occupancy,
    occupancy_stats,
    valid_stats,
)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import get_tracer
from repro.models.tcn import bake_stream_params, tcn_empty_state
from repro.sessions.scheduler import AdmissionError, SlotScheduler
from repro.sessions.state import (
    grid_init,
    grid_pspecs,
    grid_scan,
    make_grid_fused,
    pack_slot,
    parked_bytes,
    reset_slot,
    slot_park_bytes,
    unpack_slot,
)
from repro.sessions.bankpool import PagedBankPool, paged_bank_fc
from repro.sessions.rehearsal import RehearsalBuffer
from repro.sessions.tenancy import (
    TenantBank,
    bank_add_class,
    bank_clear_tenant,
    bank_fc,
    bank_init,
    bank_pack_tenant,
    bank_pspecs,
    bank_row_bytes,
    bank_unpack_tenant,
    bank_update_class,
)

NO_TENANT = -1


def _jit_highest(fn):
    """``jax.jit`` of ``fn`` traced at "highest" default matmul precision
    (the matmuls inside Pallas kernels included)."""
    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(traced)


@dataclass
class SessionRecord:
    """Minimal per-session host record; services subclass for extra fields.
    ``steps`` doubles as the fresh-session marker: a bound session with
    steps == 0 gets a zeroed column instead of a parked blob."""
    steps: int = 0
    last: dict | None = None


# ---------------------------------------------------------------------------
# Service-agnostic slot-grid core
# ---------------------------------------------------------------------------

class SlotGridService:
    """Fixed compiled slot grid + scheduler + parking lot + persistence.

    Subclasses must provide the device-state hooks:

      _pack(slot, sid) -> blob   one slot's column -> host parked blob
      _unpack(slot, blob)        parked blob -> column of ``slot``
      _reset(slot)               zero a column for a fresh session

    and may override the lifecycle hooks (_on_bind/_on_unbind/_on_close)
    and the spill/restore meta hooks (_session_spill_meta/_spill_extra/
    _restore_validate/_restore_apply/_restore_session).  All placement
    policy (free slots, LRU, pinning, cost-aware tie-breaks, admission
    back-pressure) stays in sessions/scheduler.SlotScheduler.
    """

    _session_cls = SessionRecord
    _service_name = "grid"  # metrics/trace label; subclasses override
    # services with real per-tenant state (prototype banks) set this so the
    # serving plane forwards its routing tenant into open_session too
    tenant_aware = False

    def __init__(self, n_slots: int, *, t_chunk: int = 1,
                 max_sessions: int | None = None,
                 cost_fn: Callable[[int], float] | None = None,
                 stale_window: int = 0,
                 metrics: MetricsRegistry | None = None,
                 tracer=None, device_counters: bool | None = None,
                 runtime: RuntimeConfig | None = None):
        if t_chunk < 1:
            raise ValueError(f"t_chunk must be >= 1, got {t_chunk}")
        self.n_slots = n_slots
        self.t_chunk = t_chunk
        self.sched = SlotScheduler(n_slots, max_sessions, cost_fn=cost_fn,
                                   stale_window=stale_window)
        self.parking: dict[int, dict] = {}        # sid -> host blob
        self.sessions: dict[int, Any] = {}        # sid -> session record
        self._next_sid = 0
        # -- runtime switches (configs/runtime.RuntimeConfig): ONE resolved
        # view of the historical env vars; per-field kwargs stay at the top
        # of the precedence (explicit kwarg > runtime/env > default)
        self.runtime = runtime if runtime is not None \
            else RuntimeConfig.resolve()
        # -- telemetry plane (repro.obs): every counter the service keeps
        # lives in ONE registry; pass ``metrics=`` to share a registry
        # across services (a multi-worker front-end), default is private.
        # The tracer defaults to the process-global one (REPRO_TRACE=path
        # enables it); ``device_counters`` compiles the instrumented scan
        # twins (extra in-jit stats outputs, bit-identical session state).
        self.metrics_registry = metrics if metrics is not None \
            else MetricsRegistry()
        self.tracer = tracer if tracer is not None else get_tracer()
        self.device_counters = bool(
            self.runtime.pick("device_counters", device_counters))
        svc = self._service_name
        reg = self.metrics_registry
        self._c_dispatches = reg.counter("dispatches_total", service=svc)
        self._c_evictions = reg.counter("evictions_total", service=svc)
        self._g_bound = reg.gauge("sessions_bound", service=svc)
        self._g_parked = reg.gauge("sessions_parked", service=svc)
        # parking-lot host footprint, maintained incrementally at the blob
        # store/take sites (summing every blob per mutation would be
        # O(parked * leaves) on the bind path)
        self._g_parked_bytes = reg.gauge("parked_bytes", service=svc)
        self._parked_blob_bytes: dict[int, int] = {}  # sid -> host bytes
        self._lat_hists: dict[str, Any] = {}  # shape -> Histogram (cached)

    # -- parking-lot accounting ---------------------------------------------
    @property
    def parked_blob_bytes(self) -> int:
        """Total host bytes of the parking lot (exact: sums each parked
        blob's array bytes, nibble-packed and block-granular blobs count
        as stored)."""
        return sum(self._parked_blob_bytes.values())

    def _park_store(self, sid: int, blob) -> None:
        self.parking[sid] = blob
        self._parked_blob_bytes[sid] = parked_bytes(blob)
        self._g_parked_bytes.set(self.parked_blob_bytes)

    def _park_take(self, sid: int, default=None):
        blob = self.parking.pop(sid, default)
        if self._parked_blob_bytes.pop(sid, None) is not None:
            self._g_parked_bytes.set(self.parked_blob_bytes)
        return blob

    # -- telemetry ----------------------------------------------------------
    # Backward-compat surface for the historical bare-int counters: reads
    # and writes route through the registry, so ``svc.dispatches`` and
    # ``svc.metrics()["dispatches_total"]`` can never disagree.
    @property
    def dispatches(self) -> int:
        """Jitted calls (the amortization metric)."""
        return int(self._c_dispatches.value)

    @dispatches.setter
    def dispatches(self, v: int) -> None:
        self._c_dispatches.value = v

    @property
    def evictions(self) -> int:
        return int(self._c_evictions.value)

    @evictions.setter
    def evictions(self, v: int) -> None:
        self._c_evictions.value = v

    def metrics(self) -> dict:
        """JSON snapshot of the service's metrics registry."""
        return self.metrics_registry.snapshot()

    def _latency_hist(self, shape: str):
        h = self._lat_hists.get(shape)
        if h is None:
            h = self.metrics_registry.histogram(
                "dispatch_latency_us", service=self._service_name,
                shape=shape)
            self._lat_hists[shape] = h
        return h

    def _record_dispatch(self, seconds: float, shape: str) -> None:
        """One jitted call completed: count it, record wall time in the
        per-compiled-shape log2 histogram, refresh occupancy gauges."""
        self._c_dispatches.inc()
        self._latency_hist(shape).record(seconds * 1e6)
        self._g_bound.set(len(self.sched.slot_of))
        self._g_parked.set(len(self.parking))

    def _ingest_occupancy(self, stats) -> None:
        """Fold one dispatch's device-side occupancy vector
        (obs.device.occupancy_stats) into the registry."""
        occ = decode_occupancy(stats)
        svc = self._service_name
        reg = self.metrics_registry
        reg.counter("device_live_steps_total", service=svc).inc(
            occ["live_steps"])
        reg.counter("device_masked_steps_total", service=svc).inc(
            occ["total_steps"] - occ["live_steps"])
        reg.gauge("device_lane_occupancy", service=svc).set(
            occ["lane_occupancy"])
        reg.gauge("device_pad_waste", service=svc).set(occ["pad_waste"])
        reg.gauge("device_live_step_ratio", service=svc).set(
            occ["live_step_ratio"])

    # -- state hooks (subclass responsibility) ------------------------------
    def _pack(self, slot: int, sid: int) -> dict:
        raise NotImplementedError

    def _unpack(self, slot: int, blob: dict) -> None:
        raise NotImplementedError

    def _reset(self, slot: int) -> None:
        raise NotImplementedError

    def _on_bind(self, sid: int, slot: int) -> None:
        pass

    def _on_unbind(self, slot: int) -> None:
        pass

    def _on_evict(self, sid: int, slot: int) -> None:
        """Lifecycle hook for the eviction path: ``sid`` was just packed
        off ``slot`` to make room for another session.  Distinct from
        ``_on_unbind`` (park/close), which fires when a slot goes truly
        idle — an evicted slot is re-occupied immediately."""

    def _on_close(self, sid: int, sess) -> None:
        pass

    # -- lifecycle ----------------------------------------------------------
    def _alloc_sid(self) -> int:
        sid = self._next_sid
        self._next_sid += 1
        return sid

    def open_session(self, **kw) -> int:
        """Admit a session and place it on a slot (may evict an idle one)."""
        sid = self._alloc_sid()
        self.sched.admit(sid)  # may raise AdmissionError (back-pressure)
        self.sessions[sid] = self._session_cls(**kw)
        self._bind(sid)
        return sid

    def _bind(self, sid: int, pinned: set[int] = frozenset()) -> int:
        with self.tracer.span("bind", cat=self._service_name, sid=sid):
            slot, evicted = self.sched.bind(sid, pinned)
            if evicted is not None:
                with self.tracer.span("pack", cat=self._service_name,
                                      sid=evicted, slot=slot):
                    blob = self._pack(slot, evicted)
                self._park_store(evicted, blob)
                self._c_evictions.inc()
                self._on_evict(evicted, slot)
                if self.tracer.enabled:
                    cost = self.sched.cost_fn(evicted) \
                        if self.sched.cost_fn is not None else None
                    self.tracer.instant("evict", cat=self._service_name,
                                        victim=evicted, slot=slot,
                                        for_sid=sid, park_cost=cost)
            if sid in self.parking:
                with self.tracer.span("unpack", cat=self._service_name,
                                      sid=sid, slot=slot):
                    self._unpack(slot, self._park_take(sid))
                self.tracer.instant("resume", cat=self._service_name,
                                    sid=sid, slot=slot)
            elif self.sessions[sid].steps == 0:
                with self.tracer.span("reset", cat=self._service_name,
                                      sid=sid, slot=slot):
                    self._reset(slot)
            else:  # rebinding after evicted==None cannot lose state
                raise AssertionError("bound session missing parked state")
            self._on_bind(sid, slot)
        return slot

    def park(self, sid: int) -> None:
        """Explicitly swap a session's slot column to host memory.
        Raises ``KeyError`` for a sid that was never admitted (the same
        contract as ``_touch_and_bind``); parking an already-parked
        session stays a no-op."""
        if sid not in self.sessions:
            raise KeyError(f"unknown session {sid}")
        slot = self.sched.park(sid)
        if slot is not None:
            with self.tracer.span("park", cat=self._service_name,
                                  sid=sid, slot=slot):
                self._park_store(sid, self._pack(slot, sid))
            self._on_unbind(slot)

    def resume(self, sid: int) -> None:
        """Eagerly bind a parked session back onto a slot WITHOUT advancing
        it — the inverse of ``park``.  ``push`` resumes lazily as part of
        its pre-dispatch placement, so calling this first is never
        required; a front-end uses it to prepay the unpack cost before a
        latency-sensitive push.  Raises ``KeyError`` for a sid that was
        never admitted; resuming a bound session just refreshes its LRU
        clock."""
        if sid not in self.sessions:
            raise KeyError(f"unknown session {sid}")
        self.sched.touch(sid)
        if not self.sched.is_bound(sid):
            self._bind(sid)

    def push(self, work: dict[int, Any]) -> dict[int, Any]:
        """Advance sessions by one ragged batch of work — the protocol hot
        path (sessions.SessionService).  The payload type is the service's:
        the TCN service takes ``{sid: (t, C_in) chunk}``, the LM service
        ``{sid: n_tokens}``.  Concrete services alias their historical
        verb (``push_audio`` / ``decode``) onto this name."""
        raise NotImplementedError

    def close(self, sid: int) -> None:
        slot = self.sched.release(sid)
        if slot is not None:
            self._on_unbind(slot)
        self._park_take(sid)
        sess = self.sessions.pop(sid)
        self._on_close(sid, sess)

    def enroll(self, sid: int, shots, **kwargs) -> int:
        """Protocol verb (sessions.SessionService): streaming on-device
        learning.  Services with a learnable head override this (the TCN
        façade aliases ``enroll_shots``); everyone else keeps the protocol
        surface but refuses the verb."""
        raise NotImplementedError(
            f"{self._service_name} service does not support enrollment")

    def _touch_and_bind(self, sids) -> None:
        """Pre-dispatch placement: pin this tick's sessions, then bind any
        that are parked (possibly evicting idle neighbors)."""
        pinned = set(sids)
        for sid in sids:
            if sid not in self.sessions:
                raise KeyError(f"unknown session {sid}")
            self.sched.touch(sid)
            if not self.sched.is_bound(sid):
                self._bind(sid, pinned)

    # -- chunk padding buckets ----------------------------------------------
    def _tick_len(self, remaining: int) -> int:
        """Bucketed tick length: full T_chunk while enough work remains,
        else the next power of two — bounds compiled programs to
        log2(T_chunk)+1 shapes instead of one per ragged length."""
        if remaining >= self.t_chunk:
            return self.t_chunk
        n = 1
        while n < remaining:
            n <<= 1
        return min(n, self.t_chunk)

    # -- persistence --------------------------------------------------------
    def _session_spill_meta(self, sid: int) -> dict:
        return {"steps": self.sessions[sid].steps}

    def _spill_extra(self) -> dict:
        return {}

    def _restore_validate(self, parking: dict, meta: dict) -> None:
        pass

    def _restore_apply(self, meta: dict) -> None:
        pass

    def _restore_session(self, info: dict):
        return self._session_cls(steps=int(info.get("steps", 0)))

    def spill_parking(self, path: str, *, include_bound: bool = False) -> str:
        """Persist the parking lot to disk through checkpoint/store, so
        sessions survive process restarts.  ``include_bound=True`` parks
        every bound session first — a full drain for planned shutdown."""
        if include_bound:
            for sid in list(self.sched.slot_of):
                self.park(sid)
        meta = {"next_sid": self._next_sid,
                "sessions": {str(sid): self._session_spill_meta(sid)
                             for sid in self.parking}}
        meta.update(self._spill_extra())
        return save_sessions(path, self.parking, meta)

    def restore_parking(self, path: str) -> list[int]:
        """Adopt a spilled parking lot into this (possibly fresh) service:
        sessions re-enter parked, with their sids and host records intact;
        the next push resumes them bit-identically.  Returns the restored
        sids.

        All-or-nothing: every check (sid collisions, admission capacity,
        service-specific validation) runs BEFORE the first mutation, so a
        refused restore leaves the service untouched."""
        parking, meta = load_sessions(path)
        meta = meta or {"next_sid": 0, "sessions": {}}
        for sid in sorted(parking):
            if sid in self.sessions:
                raise ValueError(f"session {sid} already live; refuse to "
                                 "overwrite on restore")
        cap = self.sched.max_sessions
        if cap is not None and self.sched.live_sessions + len(parking) > cap:
            raise AdmissionError(
                f"restoring {len(parking)} sessions would exceed capacity "
                f"({self.sched.live_sessions}/{cap} live)")
        self._restore_validate(parking, meta)
        self._restore_apply(meta)
        restored = []
        for sid, parked in sorted(parking.items()):
            info = meta["sessions"].get(str(sid), {})
            self.sched.admit(sid)
            self.sessions[sid] = self._restore_session(info)
            self._park_store(sid, parked)
            restored.append(sid)
        self._next_sid = max(self._next_sid, int(meta.get("next_sid", 0)))
        self._post_restore(restored, meta)
        return restored

    def _post_restore(self, restored: list[int], meta: dict) -> None:
        """Hook: runs once after a successful restore with the spill meta
        (so subclasses never need to re-read the file)."""

    # -- live handoff / crash recovery --------------------------------------
    # The serving plane's fault-tolerance layer (serving/plane.py drain/
    # recover/steal + the per-op spill journal) is built on these four
    # verbs.  They reuse the exact park/spill machinery above, so every
    # bit-identity property of park/resume carries over to handoff.

    def export_session(self, sid: int) -> tuple[dict, dict]:
        """Snapshot one live session as ``(parked blob, spill meta)``
        WITHOUT closing it — the plane's spill-epoch primitive.  A bound
        session is parked first (park/resume is bit-identical, so the
        snapshot has no behavioral effect); it stays live and lazily
        re-binds on its next push.  Raises ``KeyError`` for unknown sids
        and ``RuntimeError`` for sessions with no packable state (e.g. a
        retired LM session whose slot was already freed)."""
        if sid not in self.sessions:
            raise KeyError(f"unknown session {sid}")
        self.park(sid)
        if sid not in self.parking:
            raise RuntimeError(f"session {sid} holds no packable state "
                               "(retired?); nothing to export")
        return self.parking[sid], self._session_spill_meta(sid)

    def detach_session(self, sid: int) -> tuple[dict, dict]:
        """Remove a session from this service and return its packed
        ``(blob, meta)`` for adoption elsewhere (drain handoff / work
        stealing).  Unlike ``close``, the session is NOT ending —
        ``_on_close`` side effects (dedicated-tenant teardown) do not
        fire; the meta carries everything the peer needs to recreate the
        host record via ``adopt_session``."""
        blob, meta = self.export_session(sid)
        self._park_take(sid)
        self.sched.release(sid)
        self.sessions.pop(sid)
        self._on_detach(sid)
        return blob, meta

    def _on_detach(self, sid: int) -> None:
        """Hook: the session just left for another worker (NOT a close)."""

    def adopt_session(self, blob: dict, meta: dict) -> int:
        """Admit a session exported by a peer's ``detach_session`` /
        ``export_session`` under a FRESH local sid (worker-local ids from
        different services may collide).  The session enters parked; its
        next push resumes it bit-identically.  All validation (geometry,
        admission capacity) runs before the first mutation."""
        meta = dict(meta or {})
        self._adopt_validate(blob, meta)
        cap = self.sched.max_sessions
        if cap is not None and self.sched.live_sessions + 1 > cap:
            raise AdmissionError(
                f"adopting a session would exceed capacity "
                f"({self.sched.live_sessions}/{cap} live)")
        sid = self._alloc_sid()
        self.sched.admit(sid)
        self.sessions[sid] = self._restore_session(meta)
        self._park_store(sid, blob)
        self._on_adopt(sid, meta)
        return sid

    def _adopt_validate(self, blob: dict, meta: dict) -> None:
        """Hook: refuse a geometry-incompatible blob BEFORE mutation."""

    def _on_adopt(self, sid: int, meta: dict) -> None:
        """Hook: runs once after a successful single-session adoption."""

    # -- tenant-state handoff (tenant-aware services override) --------------
    def live_tenants(self) -> list:
        """Tenant ids currently holding state on this service; the base
        grid has none (the plane guards with ``tenant_aware`` anyway)."""
        return []

    def export_tenant(self, tenant) -> dict:
        raise NotImplementedError(
            f"{self._service_name} service has no per-tenant state")

    def adopt_tenant(self, tenant, blob: dict) -> int:
        raise NotImplementedError(
            f"{self._service_name} service has no per-tenant state")

    # -- introspection ------------------------------------------------------
    def _extra_stats(self) -> dict:
        return {}

    def _slot_state_bytes(self) -> int:
        """STRUCTURAL parked footprint of one session (content-independent)
        — part of the frozen stats schema, so every service must price it."""
        raise NotImplementedError

    def stats(self) -> dict:
        """Introspection snapshot.  The leading keys are the FROZEN shared
        schema (sessions.STATS_SCHEMA) every service must emit identically
        — the protocol conformance test asserts it, so the services can
        never drift apart again; ``_extra_stats`` appends service-specific
        extras under keys outside the schema."""
        return {
            "service": self._service_name,
            "n_slots": self.n_slots,
            "t_chunk": self.t_chunk,
            "bound": len(self.sched.slot_of),
            "parked": len(self.parking),
            "live_sessions": self.sched.live_sessions,
            "evictions": self.evictions,
            "dispatches": self.dispatches,
            "parked_blob_bytes": self.parked_blob_bytes,
            "slot_state_bytes": self._slot_state_bytes(),
            **self._extra_stats(),
        }


# ---------------------------------------------------------------------------
# The TCN streaming service
# ---------------------------------------------------------------------------

@dataclass
class _Session(SessionRecord):
    tenant: int = NO_TENANT
    dedicated: bool = False  # tenant row was created for this session


class StreamSessionService(SlotGridService):
    """Multi-tenant streaming TCN service over a fixed slot grid."""

    _session_cls = _Session
    _service_name = "tcn"
    tenant_aware = True  # plane routing tenants bind real bank rows here

    def __init__(self, bundle, params, bn_state=None, *, n_slots: int = 8,
                 max_tenants: int = 8, max_ways: int = 8,
                 max_sessions: int | None = None, quantize: bool = False,
                 t_chunk: int = 16, mesh=None,
                 cost_fn: Callable[[int], float] | None = None,
                 stale_window: int = 0, fused: bool | None = None,
                 kernel_backend: str | None = None,
                 paged_bank: bool = False, bank_block_ways: int = 4,
                 bank_blocks: int | None = None, rehearsal_cap: int = 0,
                 metrics: MetricsRegistry | None = None, tracer=None,
                 device_counters: bool | None = None,
                 runtime: RuntimeConfig | None = None):
        super().__init__(n_slots, t_chunk=t_chunk, max_sessions=max_sessions,
                         cost_fn=cost_fn, stale_window=stale_window,
                         metrics=metrics, tracer=tracer,
                         device_counters=device_counters, runtime=runtime)
        cfg = bundle.cfg
        self.cfg = cfg
        self.max_ways = max_ways
        self.quantize = quantize
        # Fused kernel fast path (kernels/tcn_block.py): fold BN (and bake
        # the log2 weight quantization) ONCE at service construction, then
        # advance chunks through per-block fused kernels instead of the
        # per-sample scan body.  Opt-in: BN folding reassociates the BN
        # chain by one ULP, so a fused service's outputs are allclose —
        # not bit-identical — to an unfused service on the same RAW
        # params.  On the baked params the fused and scan executors ARE
        # bit-identical (tests/test_streaming_chunk.py), so park/resume
        # and cross-chunk-size exactness are preserved within a service.
        # Switch resolution: explicit kwarg > runtime/REPRO_TCN_FUSED >
        # off (configs/runtime.RuntimeConfig, the consolidated parser).
        fused = bool(self.runtime.pick("fused", fused))
        kernel_backend = self.runtime.pick("kernel_backend", kernel_backend)
        self.fused = fused
        bn_state = bn_state if bn_state is not None else tcn_empty_state(cfg)
        self._fused_params = None
        if fused:
            params, bn_state, self._fused_params = bake_stream_params(
                params, bn_state, cfg, quantize=quantize)

        self.states = grid_init(cfg, n_slots)
        # Bank layout: dense (T, max_ways, V) enroll-once table, or the
        # paged pool (sessions/bankpool.py) where way rows are allocated
        # block-at-a-time as tenants enroll past each block_ways boundary
        # and parked tenants hold zero device rows.  max_ways becomes the
        # per-tenant GROWTH CAP in paged mode (rounded up to whole blocks)
        # rather than a pre-paid allocation.
        self.paged_bank = bool(paged_bank)
        if self.paged_bank:
            mtb = -(-max_ways // bank_block_ways)  # ceil
            if bank_blocks is None:
                bank_blocks = max_tenants * mtb
            self.bankpool = PagedBankPool(bank_blocks, bank_block_ways,
                                          cfg.embed_dim, mtb)
            self.bank = None
        else:
            self.bankpool = None
            self.bank = bank_init(max_tenants, max_ways, cfg.embed_dim)
        if mesh is not None:  # shard slots over data, banks over model
            from jax.sharding import NamedSharding
            nd = lambda p: NamedSharding(mesh, p)
            self.states = jax.device_put(
                self.states, jax.tree.map(nd, grid_pspecs(cfg, mesh, n_slots)))
            if self.bank is not None:
                self.bank = jax.device_put(
                    self.bank, jax.tree.map(nd, bank_pspecs(self.bank, mesh)))
        self.mesh = mesh
        self.tenant_of_slot = np.full(n_slots, NO_TENANT, np.int32)
        self._free_tenants = list(range(max_tenants))
        self._tenant_ways = np.zeros(max_tenants, np.int32)  # host mirror
        # label-keyed streaming enrollment: per-tenant class registry so
        # repeated enroll(label=...) calls fold into ONE way's running mean
        self._tenant_labels: dict[int, dict] = {}
        # bounded latent-replay memory (sessions/rehearsal.py)
        self.rehearsal = RehearsalBuffer(rehearsal_cap) \
            if rehearsal_cap > 0 else None
        reg = self.metrics_registry
        self._c_enrolls = reg.counter("enrolls_total", service="tcn")
        self._c_enroll_shots = reg.counter("enroll_shots_total", service="tcn")
        self._h_enroll = reg.histogram("enroll_latency_us", service="tcn")
        if self.paged_bank:
            self._g_pool_live = reg.gauge("bank_pool_blocks_live",
                                          service="tcn")
            self._g_pool_free = reg.gauge("bank_pool_blocks_free",
                                          service="tcn")
            self._update_pool_gauges()
        if self.rehearsal is not None:
            self._g_rehearsal_bytes = reg.gauge("rehearsal_bytes",
                                                service="tcn")

        # params/bn enter the jitted scan as ARGUMENTS, not closure
        # constants: XLA constant-folds closure BN chains differently per
        # compiled chunk bucket, which would break the bit-exactness
        # contract between the T=1 and T=t_chunk programs (runtime data is
        # never reassociated; verified in tests/test_streaming_chunk.py).
        self._params = params
        self._bn = bn_state

        if self.paged_bank:
            # per-slot FC tables gathered through the block tables; the
            # row math is store_fc verbatim and the contraction is the
            # SAME pn_logits_banked einsum as the dense path (indexed by
            # slot instead of tenant), so at equal way counts the logits
            # are bit-identical to the dense bank path (tested)
            def _banked(emb, pool_s, pool_c, tables, ways):
                w, b = paged_bank_fc(pool_s, pool_c, tables, ways)
                s, t = emb.shape[0], emb.shape[1]
                tl = pn_logits_banked(emb.reshape(s * t, emb.shape[-1]), w, b,
                                      jnp.repeat(jnp.arange(s), t))
                return tl.reshape(s, t, -1)
        else:
            def _banked(emb, bank, tenant_ids):
                w, b = bank_fc(bank)
                s, t = emb.shape[0], emb.shape[1]
                tl = pn_logits_banked(emb.reshape(s * t, emb.shape[-1]), w, b,
                                      jnp.repeat(tenant_ids, t))
                return tl.reshape(s, t, -1)

        # device counters ride the SAME dispatch as extra outputs (one
        # in-jit reduce of the validity mask) — the state math is the
        # identical op graph, so instrumented and plain services stay
        # bit-identical on session state (tests/test_obs.py asserts it)
        dev = self.device_counters

        def _scan(p, bn, states, x, valid, *bank_args):
            new_states, emb, logits = grid_scan(
                p, bn, cfg, states, x, valid, quantize=quantize)
            out = (new_states, emb, logits, _banked(emb, *bank_args))
            return out + (valid_stats(valid),) if dev else out

        # u4 sessions round every activation onto the act_scale grid.  The
        # bf16 passes of a TPU's default f32 matmul move values across its
        # rounding points, and each flipped activation propagates down the
        # stream, so quantized programs trace at "highest" (f32) matmul
        # precision; Mosaic has no "high" (bf16_3x) dot, and outside the
        # kernels "high" still moves u4 codes.  The CPU computes f32
        # either way.
        jit = _jit_highest if quantize else jax.jit
        self._scan = jit(_scan)
        if fused:
            fused_chunk = make_grid_fused(cfg, quantize=quantize,
                                          backend=kernel_backend, mesh=mesh)

            def _scan_fused(fp, states, x, lengths, *bank_args):
                new_states, emb, logits = fused_chunk(fp, states, x, lengths)
                out = (new_states, emb, logits,
                       _banked(emb, *bank_args))
                return out + (occupancy_stats(lengths, x.shape[1]),) \
                    if dev else out

            self._scan_fused = jit(_scan_fused)
        # shot embedding for enrollment — the TCN bundle's embed_fn honours
        # the service's BN stats and quantize mode
        self._embed = jit(lambda x: bundle.embed_fn(
            params, {"x": x}, state=bn_state, quantize=quantize))

    # -- slot-column state hooks --------------------------------------------
    def _pack(self, slot: int, sid: int) -> dict:
        return pack_slot(self.states, slot, pack_u4=self.quantize,
                         act_scale=self.cfg.act_scale)

    def _unpack(self, slot: int, blob: dict) -> None:
        self.states = unpack_slot(self.states, slot, blob)

    def _reset(self, slot: int) -> None:
        self.states = reset_slot(self.states, slot)

    def _on_bind(self, sid: int, slot: int) -> None:
        self.tenant_of_slot[slot] = self.sessions[sid].tenant

    def _on_unbind(self, slot: int) -> None:
        tenant = int(self.tenant_of_slot[slot])
        self.tenant_of_slot[slot] = NO_TENANT
        self._maybe_park_tenant(tenant)

    def _on_evict(self, sid: int, slot: int) -> None:
        # the eviction path bypasses _on_unbind (the slot is re-occupied
        # immediately), but the paged bank still needs to know when a
        # tenant's LAST bound session left the grid
        self._maybe_park_tenant(int(self.tenant_of_slot[slot]))

    # -- tenants ------------------------------------------------------------
    def _tenant_idle(self, tenant: int) -> bool:
        """True when no BOUND session belongs to ``tenant`` (parked
        sessions don't hold bank residency)."""
        return all(self.sessions[sid].tenant != tenant
                   for sid in self.sched.slot_of)

    def _maybe_park_tenant(self, tenant: int) -> None:
        """Paged mode: spill an idle tenant's bank rows to host so parked
        tenants hold zero device rows (the pool invariant)."""
        if (self.paged_bank and tenant != NO_TENANT
                and tenant in self.bankpool.n_ways
                and self.bankpool.is_resident(tenant)
                and self._tenant_idle(tenant)):
            with self.tracer.span("bank_park", cat="tcn", tenant=tenant):
                self.bankpool.park(tenant)
            self._update_pool_gauges()

    def _ensure_bank_resident(self, tenant: int) -> None:
        if self.paged_bank and not self.bankpool.is_resident(tenant):
            with self.tracer.span("bank_unpark", cat="tcn", tenant=tenant):
                self.bankpool.unpark(tenant)  # may raise PoolExhausted
            self._update_pool_gauges()

    def _update_pool_gauges(self) -> None:
        self._g_pool_live.set(self.bankpool.pool.n_live)
        self._g_pool_free.set(self.bankpool.pool.n_free)

    def create_tenant(self) -> int:
        if not self._free_tenants:
            raise RuntimeError("tenant bank full")
        tenant = self._free_tenants.pop(0)
        if self.paged_bank:
            self.bankpool.create(tenant)
        return tenant

    def close_tenant(self, tenant: int) -> None:
        if any(s.tenant == tenant for s in self.sessions.values()):
            raise RuntimeError(f"tenant {tenant} still has open sessions")
        if self.paged_bank:
            self.bankpool.drop(tenant)
            self._update_pool_gauges()
        else:
            self.bank = bank_clear_tenant(self.bank, tenant)
        self._tenant_labels.pop(tenant, None)
        if self.rehearsal is not None:
            self.rehearsal.drop(tenant)
        self._tenant_ways[tenant] = 0
        self._free_tenants.append(tenant)

    # -- session lifecycle --------------------------------------------------
    def open_session(self, tenant: int | None = NO_TENANT) -> int:
        """Admit a session.  ``tenant=None`` creates a dedicated tenant
        (freed again when the session closes); ``NO_TENANT`` (default)
        classifies with the shared global head."""
        dedicated = tenant is None
        claimed = dedicated
        if dedicated:
            tenant = self.create_tenant()
        elif tenant != NO_TENANT:
            if not 0 <= tenant < len(self._tenant_ways):
                raise ValueError(
                    f"tenant {tenant} out of range [0, {len(self._tenant_ways)})")
            if tenant in self._free_tenants:  # claim an uncreated row
                self._free_tenants.remove(tenant)
                if self.paged_bank:
                    self.bankpool.create(tenant)
                claimed = True
        sid = self._alloc_sid()
        try:
            self.sched.admit(sid)  # may raise AdmissionError (back-pressure)
        except Exception:
            if claimed:  # don't leak the tenant row on refused admission
                if self.paged_bank:
                    self.bankpool.drop(tenant)
                self._free_tenants.insert(0, tenant)
            raise
        self.sessions[sid] = _Session(tenant=tenant, dedicated=dedicated)
        self._bind(sid)
        return sid

    def _on_close(self, sid: int, sess) -> None:
        # a dedicated tenant row dies with its last session: if other
        # sessions share the row, ownership passes to one of them so the
        # row is still freed when the final sharer closes
        if sess.dedicated:
            sharers = [s for s in self.sessions.values()
                       if s.tenant == sess.tenant]
            if sharers:
                sharers[0].dedicated = True
            else:
                self.close_tenant(sess.tenant)

    # -- persistence hooks ---------------------------------------------------
    def _session_spill_meta(self, sid: int) -> dict:
        s = self.sessions[sid]
        return {"tenant": s.tenant, "dedicated": s.dedicated, "steps": s.steps}

    def _spill_extra(self) -> dict:
        tenant_meta = {}
        for sid in self.parking:
            t = self.sessions[sid].tenant
            if t == NO_TENANT or str(t) in tenant_meta:
                continue
            if self.paged_bank:
                tenant_meta[str(t)] = self.bankpool.pack(t)
            else:
                row = bank_pack_tenant(self.bank, t)
                tenant_meta[str(t)] = {
                    "s_sums": row["s_sums"].tolist(),
                    "counts": row["counts"].tolist(),
                    "n_ways": int(self._tenant_ways[t]),
                }
        return {"tenants": tenant_meta}

    def _restore_validate(self, parking: dict, meta: dict) -> None:
        for t_str in meta.get("tenants", {}):
            t = int(t_str)
            if t >= len(self._tenant_ways):
                raise ValueError(f"spill references tenant {t} beyond "
                                 f"max_tenants={len(self._tenant_ways)}")
            if t not in self._free_tenants:
                raise ValueError(f"tenant {t} already in use; refuse to "
                                 "overwrite its prototype row on restore")

    def _restore_apply(self, meta: dict) -> None:
        for t_str, row in meta.get("tenants", {}).items():
            t = int(t_str)
            self._free_tenants.remove(t)
            if self.paged_bank:
                # adopted PARKED (zero device rows); the first push or
                # enroll re-establishes residency
                self.bankpool.adopt(t, row)
            else:
                self.bank = bank_unpack_tenant(self.bank, t, {
                    "s_sums": np.asarray(row["s_sums"], np.float32),
                    "counts": np.asarray(row["counts"], np.float32),
                    "n_ways": np.asarray(row["n_ways"], np.int32)})
            self._tenant_ways[t] = int(row["n_ways"])

    def _restore_session(self, info: dict):
        return _Session(tenant=int(info.get("tenant", NO_TENANT)),
                        dedicated=bool(info.get("dedicated", False)),
                        steps=int(info.get("steps", 0)))

    # -- the hot path -------------------------------------------------------
    def push_audio(self, chunks: dict[int, Any]) -> dict[int, dict]:
        """Advance sessions by ragged time chunks.

        chunks: {sid: x} where x is a (t_i, C_in) chunk or a single (C_in,)
        sample (the T=1 special case).  All pushed sessions advance through
        chunked ``grid_scan`` dispatches over the compiled (S, T_chunk)
        grid; sessions absent from ``chunks`` (and the padded tail of short
        chunks) stay bit-frozen.  Parked sessions are resumed first
        (possibly evicting idle ones).

        Returns {sid: result}.  For a (t_i, C_in) chunk the result carries
        per-sample sequences — emb (t_i, V), logits (t_i, n),
        tenant_logits (t_i, ways) | None — plus the end-of-chunk
        classification ``pred`` and cumulative ``step``.  For a (C_in,)
        sample the historical surface is kept: emb (V,), logits (n,)."""
        tr = self.tracer
        with tr.span("prepare", cat="tcn", lanes=len(chunks)):
            arrs, scalar = self._validate_chunks(chunks)
            self._touch_and_bind(chunks)
            if self.paged_bank:
                # every pushed session's tenant must hold its bank rows on
                # device before the dispatch reads them through the tables
                for sid in arrs:
                    t = self.sessions[sid].tenant
                    if t != NO_TENANT:
                        self._ensure_bank_resident(t)
            slot_of = {sid: self.sched.slot_of[sid] for sid in arrs}
            lens = {sid: a.shape[0] for sid, a in arrs.items()}
            max_len = max(lens.values())
            tick = self._tick_inputs(arrs, slot_of, 0, max_len)
        pieces = {sid: [] for sid in arrs}  # per-tick (emb, logits, tl) slices
        off = 0
        while True:
            t_pad, x, tick_lens, bank_args = tick
            shape = f"T{t_pad}"
            dev_stats = None
            t0 = time.perf_counter()
            with tr.span("dispatch", cat="tcn", shape=shape,
                         lanes=len(arrs), fused=self.fused):
                with tr.span("launch", cat="tcn"):
                    if self.fused:
                        self.states, emb, logits, tlogits, *dev = \
                            self._scan_fused(
                                self._fused_params, self.states,
                                jnp.asarray(x), jnp.asarray(tick_lens),
                                *bank_args)
                    else:
                        valid = np.arange(t_pad)[None, :] < tick_lens[:, None]
                        self.states, emb, logits, tlogits, *dev = self._scan(
                            self._params, self._bn, self.states,
                            jnp.asarray(x), jnp.asarray(valid), *bank_args)
                if tr.enabled:   # the device's time apart from the copies
                    with tr.span("device_wait", cat="tcn"):
                        jax.block_until_ready((emb, logits, tlogits, dev))
                with tr.span("fetch", cat="tcn"):
                    emb, logits, tlogits = (np.asarray(emb),
                                            np.asarray(logits),
                                            np.asarray(tlogits))
                    if self.paged_bank:
                        # table width is block-granular (>= max_ways); keep
                        # the result surface mode-independent
                        tlogits = tlogits[..., :self.max_ways]
                    if dev:
                        dev_stats = np.asarray(dev[0])
            self._record_dispatch(time.perf_counter() - t0, shape)
            if dev_stats is not None:
                self._ingest_occupancy(dev_stats)
            for sid in arrs:
                n = min(max(lens[sid] - off, 0), t_pad)
                if n:
                    s = slot_of[sid]
                    pieces[sid].append(
                        (emb[s, :n], logits[s, :n], tlogits[s, :n]))
            off += t_pad
            if off >= max_len:
                break
            with tr.span("prepare", cat="tcn", lanes=len(arrs)):
                tick = self._tick_inputs(arrs, slot_of, off, max_len)

        with tr.span("assemble", cat="tcn", lanes=len(arrs)):
            out = {}
            for sid in arrs:
                sess = self.sessions[sid]
                sess.steps += lens[sid]
                e, l, tl = (np.concatenate([p[i] for p in pieces[sid]])
                            for i in range(3))
                personalized = (sess.tenant != NO_TENANT
                                and self._tenant_ways[sess.tenant] > 0)
                head = tl if personalized else l
                if scalar[sid]:
                    res = {"emb": e[-1], "logits": l[-1],
                           "tenant_logits": tl[-1] if personalized else None,
                           "pred": int(head[-1].argmax()), "step": sess.steps}
                else:
                    res = {"emb": e, "logits": l,
                           "tenant_logits": tl if personalized else None,
                           "pred": int(head[-1].argmax()), "step": sess.steps}
                sess.last = res
                out[sid] = res
        return out

    def _validate_chunks(self, chunks: dict[int, Any]):
        """({sid: (t, C_in) float32 chunk}, {sid: pushed as one sample})."""
        if len(chunks) > self.n_slots:
            raise ValueError(
                f"{len(chunks)} sessions pushed but only {self.n_slots} slots; "
                "split the push or grow the grid")
        c_in = self.cfg.tcn_in_channels
        arrs, scalar = {}, {}
        for sid, v in chunks.items():
            a = np.asarray(v, np.float32)
            scalar[sid] = a.ndim == 1
            if a.ndim == 1:
                a = a[None]
            if a.ndim != 2 or a.shape[1] != c_in:
                raise ValueError(
                    f"session {sid}: expected (C_in,) or (t, C_in) with "
                    f"C_in={c_in}, got shape {np.asarray(v).shape}")
            if a.shape[0] == 0:
                raise ValueError(f"session {sid}: empty chunk")
            arrs[sid] = a
        return arrs, scalar

    def _tick_inputs(self, arrs: dict, slot_of: dict, off: int,
                     max_len: int):
        """One tick's dispatch inputs from sample ``off`` on: (tick length,
        x (S, T, C_in), per-slot lengths, bank arguments)."""
        t_pad = self._tick_len(max_len - off)
        x = np.zeros((self.n_slots, t_pad, self.cfg.tcn_in_channels),
                     np.float32)
        tick_lens = np.zeros(self.n_slots, np.int32)
        for sid, a in arrs.items():
            seg = a[off:off + t_pad]
            if seg.shape[0]:
                x[slot_of[sid], :seg.shape[0]] = seg
                tick_lens[slot_of[sid]] = seg.shape[0]
        if self.paged_bank:
            tables, ways = self.bankpool.slot_tables(self.tenant_of_slot)
            bank_args = (self.bankpool.s_sums, self.bankpool.counts,
                         jnp.asarray(tables), jnp.asarray(ways))
        else:
            bank_args = (self.bank, jnp.asarray(self.tenant_of_slot))
        return t_pad, x, tick_lens, bank_args

    # protocol verb (sessions.SessionService): the TCN payload is audio
    push = push_audio

    # -- FSL / CL enrollment (live, mid-stream) -----------------------------
    def enroll_shots(self, sid: int, shots, *, embedded: bool = False,
                     way: int | None = None, label=None) -> int:
        """Streaming enrollment: fold k shots into the session's tenant
        bank and return the way index.  shots: (k, T, C_in) raw clips
        (embedded via the shared backbone) or (k, V) embeddings when
        ``embedded=True``.  The tenant's very next ``push_audio``
        classifies against the updated bank.

        Three addressing modes, all incremental per-class running means
        (Eq. 6 over the s_sums/counts layout):

          * ``way=None, label=None`` — append a NEW way (one-shot CL);
          * ``way=j``                — refine an enrolled way;
          * ``label=x``              — streaming: the first enroll of a
            label appends a way, later enrolls of the same label refine
            it — the caller never tracks way indices.

        In paged-bank mode the tenant's rows grow a block at a time from
        the shared pool (PoolExhausted = back-pressure) and a parked
        tenant is made resident for the update (and re-parked if it has
        no bound sessions, preserving the zero-device-rows invariant)."""
        t0 = time.perf_counter()
        tenant = self.sessions[sid].tenant
        if tenant == NO_TENANT:
            raise ValueError("session has no tenant; open with tenant=None "
                             "or an explicit tenant id to personalize")
        tr = self.tracer
        with tr.span("enroll", cat="tcn", sid=sid, tenant=tenant):
            with tr.span("embed", cat="tcn", embedded=embedded):
                emb = jnp.asarray(shots) if embedded \
                    else self._embed(jnp.asarray(shots))
                if tr.enabled:
                    # the embedding's device time in this span; untraced,
                    # the bank update's host work overlaps it
                    jax.block_until_ready(emb)
            with tr.span("bank_update", cat="tcn"):
                way = self._bank_update(tenant, emb, way, label)
            # honest latency: the bank update must have landed on device
            with tr.span("device_wait", cat="tcn"):
                jax.block_until_ready(self.bankpool.s_sums if self.paged_bank
                                      else self.bank.s_sums)
            self._maybe_park_tenant(tenant)
        self._c_enrolls.inc()
        self._c_enroll_shots.inc(int(np.asarray(shots).shape[0]))
        self._h_enroll.record((time.perf_counter() - t0) * 1e6)
        return way

    # protocol verb (sessions.SessionService): learning is first-class
    enroll = enroll_shots

    def _bank_update(self, tenant: int, emb, way: int | None, label) -> int:
        """Fold (k, V) shot embeddings into the tenant's bank (a new way,
        or a running-mean refinement of ``way`` or ``label``'s way); keeps
        the rehearsal buffer in step.  Returns the way."""
        if label is not None:
            if way is not None:
                raise ValueError("pass way= or label=, not both")
            way = self._tenant_labels.setdefault(tenant, {}).get(label)
        if way is None:
            if self._tenant_ways[tenant] >= self.max_ways:
                raise RuntimeError(
                    f"tenant {tenant} at max_ways={self.max_ways}")
            if self.paged_bank:
                self._ensure_bank_resident(tenant)
                way = self.bankpool.add_class(tenant, emb)
                self._update_pool_gauges()
            else:
                self.bank = bank_add_class(self.bank, tenant, emb)
                way = int(self._tenant_ways[tenant])
            self._tenant_ways[tenant] += 1
            if label is not None:
                self._tenant_labels[tenant][label] = way
        else:
            if not 0 <= way < self._tenant_ways[tenant]:
                raise ValueError(
                    f"way {way} not enrolled for tenant {tenant} "
                    f"({self._tenant_ways[tenant]} ways); omit way= to "
                    "enroll")
            if self.paged_bank:
                self._ensure_bank_resident(tenant)
                self.bankpool.update_class(tenant, way, emb)
            else:
                self.bank = bank_update_class(self.bank, tenant, way, emb)
        if self.rehearsal is not None:
            self.rehearsal.add(tenant, way, np.asarray(emb))
            self._g_rehearsal_bytes.set(self.rehearsal.nbytes())
        return way

    def rehearse_tenant(self, tenant: int) -> int:
        """Rebuild every enrolled way of ``tenant`` from the bounded
        rehearsal buffer (latent replay: dequantized u4 log2 embeddings
        re-summed into prototype rows), REPLACING the exact running sums.
        Returns the number of ways rebuilt.  The served CL bench measures
        the accuracy cost of exactly this substitution."""
        if self.rehearsal is None:
            raise RuntimeError(
                "service built with rehearsal_cap=0; no buffer to replay")
        n = int(self._tenant_ways[tenant])
        if self.paged_bank:
            self._ensure_bank_resident(tenant)
        for way in range(n):
            s, k = self.rehearsal.rebuild(tenant, way, self.cfg.embed_dim)
            if self.paged_bank:
                self.bankpool.set_way(tenant, way, s, k)
            else:
                self.bank = TenantBank(
                    s_sums=self.bank.s_sums.at[tenant, way].set(
                        jnp.asarray(s)),
                    counts=self.bank.counts.at[tenant, way].set(
                        jnp.float32(k)),
                    n_ways=self.bank.n_ways)
        if self.paged_bank:
            self._maybe_park_tenant(tenant)
        return n

    # -- tenant-state handoff (serving plane drain/recover) -----------------
    def live_tenants(self) -> list[int]:
        return [t for t in range(len(self._tenant_ways))
                if t not in self._free_tenants]

    def export_tenant(self, tenant: int) -> dict:
        """Layout-free host snapshot of one tenant's learned state — the
        bank running sums truncated to enrolled ways (Eq. 6 state), the
        label->way registry, and the rehearsal reservoirs — for live
        handoff to a peer worker.  Non-destructive; either bank layout
        adopts it (the paged pool pads rows back to whole blocks)."""
        if not 0 <= tenant < len(self._tenant_ways) \
                or tenant in self._free_tenants:
            raise KeyError(f"tenant {tenant} not in use")
        n = int(self._tenant_ways[tenant])
        dim = self.cfg.embed_dim
        if self.paged_bank:
            row = self.bankpool.pack(tenant)
            s = np.asarray(row["s_sums"], np.float32).reshape(-1, dim)[:n]
            c = np.asarray(row["counts"], np.float32).reshape(-1)[:n]
        else:
            row = bank_pack_tenant(self.bank, tenant)
            s = np.asarray(row["s_sums"], np.float32)[:n]
            c = np.asarray(row["counts"], np.float32)[:n]
        blob = {"s_sums": s, "counts": c, "n_ways": n,
                "labels": dict(self._tenant_labels.get(tenant, {}))}
        if self.rehearsal is not None:
            blob["rehearsal"] = self.rehearsal.export_tenant(tenant)
        return blob

    def adopt_tenant(self, tenant: int | None, blob: dict) -> int:
        """Install a peer's ``export_tenant`` blob under ``tenant`` (must
        be a free row) or under a fresh row when ``tenant is None`` —
        dedicated tenants keep service-LOCAL ids, so the plane remaps
        them on handoff.  Returns the id actually used.  Paged banks
        adopt PARKED (zero device rows until first use)."""
        if tenant is None:
            if not self._free_tenants:
                raise RuntimeError("tenant bank full")
            tenant = self._free_tenants[0]
        if not 0 <= tenant < len(self._tenant_ways):
            raise ValueError(f"tenant {tenant} out of range "
                             f"[0, {len(self._tenant_ways)})")
        if tenant not in self._free_tenants:
            raise ValueError(f"tenant {tenant} already in use; refuse to "
                             "overwrite its prototype rows")
        n = int(blob["n_ways"])
        if n > self.max_ways:
            raise ValueError(f"blob carries {n} ways but this service caps "
                             f"at max_ways={self.max_ways}")
        dim = self.cfg.embed_dim
        s = np.asarray(blob["s_sums"], np.float32).reshape(-1, dim)[:n]
        c = np.asarray(blob["counts"], np.float32).reshape(-1)[:n]
        self._free_tenants.remove(tenant)
        if self.paged_bank:
            self.bankpool.adopt(tenant, {"s_sums": s, "counts": c,
                                         "n_ways": n})
        else:
            sp = np.zeros((self.max_ways, dim), np.float32)
            cp = np.zeros((self.max_ways,), np.float32)
            sp[:n], cp[:n] = s, c
            self.bank = bank_unpack_tenant(self.bank, tenant, {
                "s_sums": sp, "counts": cp, "n_ways": np.int32(n)})
        self._tenant_ways[tenant] = n
        if blob.get("labels"):
            self._tenant_labels[tenant] = dict(blob["labels"])
        if self.rehearsal is not None and blob.get("rehearsal"):
            self.rehearsal.adopt_tenant(tenant, blob["rehearsal"])
        return tenant

    def _adopt_validate(self, blob: dict, meta: dict) -> None:
        t = int(meta.get("tenant", NO_TENANT))
        if t == NO_TENANT:
            return
        if not 0 <= t < len(self._tenant_ways):
            raise ValueError(f"session references tenant {t} beyond "
                             f"max_tenants={len(self._tenant_ways)}")
        if t in self._free_tenants:
            raise ValueError(f"session references tenant {t} but no such "
                             "row is in use here; adopt_tenant first")

    # -- introspection ------------------------------------------------------
    def poll(self, sid: int) -> dict:
        sess = self.sessions[sid]
        return {
            "state": "active" if self.sched.is_bound(sid) else "parked",
            "slot": self.sched.slot_of.get(sid),
            "tenant": None if sess.tenant == NO_TENANT else sess.tenant,
            "n_ways": int(self._tenant_ways[sess.tenant])
                      if sess.tenant != NO_TENANT else 0,
            "steps": sess.steps,
            "last": sess.last,
        }

    def _slot_state_bytes(self) -> int:
        # structural, not content-dependent, so stable for CI tracking:
        # what one session costs in the parking lot (nibble-packed when
        # the service runs quantize=True)
        return slot_park_bytes(self.cfg, quantize=self.quantize)

    def _extra_stats(self) -> dict:
        # what one tenant's prototype row costs in a spill (the paper's
        # 26 B/way personalization-cost story); paged mode prices one
        # BLOCK (the allocation granule) and reports pool occupancy
        if self.paged_bank:
            bp = self.bankpool
            extra = {"tenant_row_bytes":
                     bp.block_ways * (self.cfg.embed_dim + 1) * 4}
            extra.update({f"bank_pool_{k}": v for k, v in bp.stats().items()})
        else:
            extra = {"tenant_row_bytes": bank_row_bytes(self.bank)}
        extra["fused"] = self.fused
        extra["paged_bank"] = self.paged_bank
        if self.rehearsal is not None:
            extra["rehearsal_bytes"] = self.rehearsal.nbytes()
        return extra
