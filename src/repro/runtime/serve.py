"""Serving runtime: prefill + decode steps with sharded KV caches, a
continuous-batching request queue, and the BAaaS service wrapper.

``make_serve_step`` builds the jit'd one-token decode step the dry-run
lowers for decode_32k / long_500k cells.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import threading
import time
from typing import Any, Callable, Deque, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.lifecycle import sanitizer
from repro.configs.base import ModelConfig
from repro.core.spans import span
from repro.layers.attention import swept_cols
from repro.models.api import Model
from repro.models.stages import paged_sweep_layers
from repro.runtime.paged import PagePoolManager, default_pool_pages
from repro.runtime.sharding import (batch_specs, cache_specs, dp_axes, named,
                                    param_specs)


def make_serve_step(model: Model):
    """serve_step(params, caches, tokens, pos) -> (logits, caches)."""

    def serve_step(params, caches, tokens, pos):
        return model.decode(params, caches, tokens, pos)

    return serve_step


def make_paged_serve_step(model: Model):
    """serve_step over the paged pool: extra (B, nb) block-table operand."""

    def serve_step(params, caches, tokens, pos, block_tables):
        return model.decode_paged(params, caches, tokens, pos, block_tables)

    return serve_step


def make_prefill_step(model: Model, max_len: int, clamp_window: bool = True):
    def prefill_step(params, batch):
        return model.prefill(params, batch, max_len,
                             clamp_window=clamp_window)
    return prefill_step


def jit_serve_step(model: Model, mesh: Mesh, batch: int, cache_len: int,
                   params_shape, caches_shape):
    """jit with shardings; seq-sharding kicks in for batch=1 long-context."""
    cfg = model.cfg
    pspecs = param_specs(cfg, params_shape, mesh)
    dp_total = np.prod([mesh.shape[a] for a in mesh.axis_names
                        if a in ("pod", "data")])
    seq_shard = batch % int(dp_total) != 0
    cspecs = cache_specs(cfg, caches_shape, mesh, batch, seq_shard=seq_shard)
    dp = dp_axes(mesh)
    tok_spec = P(dp, None) if batch % int(dp_total) == 0 else P(None, None)
    pos_spec = P(dp) if batch % int(dp_total) == 0 else P(None)
    step = make_serve_step(model)
    jitted = jax.jit(
        step,
        in_shardings=(named(mesh, pspecs), named(mesh, cspecs),
                      jax.sharding.NamedSharding(mesh, tok_spec),
                      jax.sharding.NamedSharding(mesh, pos_spec)),
        out_shardings=None,
        donate_argnums=(1,))
    return jitted, {"params": pspecs, "caches": cspecs}


# ---------------------------------------------------------------------------
# Continuous batching engine (BAaaS dataplane)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _prefill_jit(model: Model, max_len: int, full_len: bool = False):
    """One jitted prefill per (model, max_len, layout), shared across
    engines — a fleet spinning an engine up on a freshly woken device must
    not pay a new trace/compile mid-hand-off. (Model is a frozen dataclass
    of config only, so the cache key is cheap and value-equal across
    engines.) ``full_len`` builds non-ring full-length caches for windowed
    sites — the layout the paged page-splice consumes.

    Bounded: the engine is hypervisor-independent, so prefill programs
    live in this small LRU rather than the RC3E ProgramCache the gateway/
    fleet route the decode program through; 8 (model, max_len) pairs cover
    any realistic co-resident serving mix without pinning executables for
    every config a long-lived process ever touched."""
    step = make_prefill_step(model, max_len, clamp_window=not full_len)
    return jax.jit(lambda p, toks: step(p, {"tokens": toks}))


@functools.partial(jax.jit, donate_argnums=(0,))
def _splice_slot(full, one, slot):
    """Write a batch-1 prefill cache into row ``slot`` of shared caches.
    The old cache tree is donated: only one slot row changes, and without
    donation every admission would copy the entire fleet of KV buffers."""
    return jax.tree.map(
        lambda f, o: f.at[:, slot].set(o[:, 0].astype(f.dtype)), full, one)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("start",))
def _splice_pages(pool, one, pages, start: int):
    """Scatter a batch-1 full-length prefill cache into pool pages: block
    ``start + i`` of the context lands in page ``pages[i]``. Pool leaves
    are (L, P, ps, ...), prefill leaves (L, 1, max_len, ...); the pool tree
    is donated (only the touched pages change)."""
    nb = pages.shape[0]

    def put(pl_leaf, d_leaf):
        ps = pl_leaf.shape[2]
        seg = jax.lax.dynamic_slice_in_dim(d_leaf[:, 0], start * ps, nb * ps,
                                           axis=1)
        seg = seg.reshape((d_leaf.shape[0], nb, ps) + d_leaf.shape[3:])
        return pl_leaf.at[:, pages].set(seg.astype(pl_leaf.dtype))

    return jax.tree.map(put, pool, one)


@functools.partial(jax.jit, donate_argnums=(0,))
def _invalidate_pool_pages(pool, pages):
    """Reset the ``pos`` metadata of ``pages`` to -1 across every layer's
    pool. A recycled page still carries its previous occupant's positions;
    for the new owner those can look like valid causal history (stale
    K/V leaking into attention), so every allocation that does not
    overwrite the whole page must invalidate it first. Only the position
    leaves change — k/v content is dead weight once pos is -1."""
    def inv(path, leaf):
        if getattr(path[-1], "key", None) == "pos":
            return leaf.at[:, pages].set(-1)
        return leaf
    return jax.tree_util.tree_map_with_path(inv, pool)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scrub_pool_pages(pool, pages):
    """Zero-on-free: restore ``pages`` to their init state across every
    layer's pool — k/v content to 0, ``pos`` to -1, quantization scales to
    1. ``_invalidate_pool_pages`` only resets pos, which hides stale K/V
    from *attention* (masked) but not from ``export_request_pages``, whose
    whole-page gather would hand a previous tenant's residual K/V values
    to whoever receives the migration snapshot. One batched call per
    engine flush, not one per page."""
    def scrub(path, leaf):
        key = getattr(path[-1], "key", None)
        if key == "pos":
            return leaf.at[:, pages].set(-1)
        if key in ("k_scale", "v_scale"):
            return leaf.at[:, pages].set(1)
        return leaf.at[:, pages].set(0)
    return jax.tree_util.tree_map_with_path(scrub, pool)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pool, src, dst):
    """Copy-on-write detach: duplicate page ``src`` into ``dst`` across
    every layer's pool (leaves are (L, P, ps, ...); axis 1 is the page)."""
    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pool)


@jax.jit
def _argmax_tokens(logits):
    """Greedy sampling ON DEVICE: reduce (n_slots, 1, vocab) logits to
    (n_slots,) int32 token ids before they cross to the host. The engine
    step loop used to pull the full logits tensor host-side and argmax in
    numpy — a vocab-sized D2H transfer per decode step (n_slots * vocab *
    4 bytes, ~0.5 MB at vocab 32k / 4 slots) for 4 bytes of answer per
    slot."""
    with jax.named_scope("rc3e.argmax"):
        return jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, donate_argnums=(0,))
def _import_pages(pool, payload, pages):
    """Scatter a migrated request's page payload (leaves (L, nb, ps, ...))
    into freshly allocated pages of this engine's pool."""
    return jax.tree.map(
        lambda pl_leaf, seg: pl_leaf.at[:, pages].set(
            seg.astype(pl_leaf.dtype)), pool, payload)


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    tenant: str = "default"
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    submitted_at: float = dataclasses.field(default_factory=time.monotonic)
    admitted_at: Optional[float] = None   # first admission into a slot
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    finish_reason: Optional[str] = None   # "eos" | "length" | "cancelled"


@dataclasses.dataclass
class _PendingPrefill:
    """A slot admitted by the event-driven loop whose prompt prefill has
    not yet been spliced into the shared caches. The batched prefill is
    COMPUTED once at admission (one compiled call — recomputing it per
    chunk would multiply the work the chunking is meant to hide) but the
    result is only BUFFERED here; the slot is accounted ``prefill_chunk``
    context tokens per engine event and joins decode when the accounted
    chunks cover the context. Until then the slot is excluded from decode
    and page-write preparation (paged slots sit at pos -1: their decode
    rows write the null page)."""
    chunks_left: int
    buf: Any                    # batch-1 prefill caches (None: nothing to splice)
    plan: Any                   # paged AdmitPlan (None on dense engines)
    ctx_len: int                # len(prompt + replayed tokens)
    last_token: int             # final context token -> first decode input


def _req_event(req: Request, event: str) -> None:
    """Drive the request lifecycle machine (RC3E_SANITIZE=1). Keyed by the
    per-request ``scope()`` token stamped at submit time — NOT request_id,
    which is only unique within one id_counter (standalone engines each
    start at 0) — so the key travels with the object across a live
    hand-off between engines."""
    tok = getattr(req, "_san", None)
    if tok is not None:
        sanitizer.emit("request", tok, event)


class BatchingEngine:
    """Slot-based continuous batching: up to ``n_slots`` concurrent requests
    share one decode program; prefill happens per-request into its slot.

    Requests are tenant-tagged: each tenant has its own FIFO queue, and
    admission runs weighted deficit round-robin across tenants (see
    ``_pop_next_request``) so one tenant's backlog — even a deliberate
    long-prompt flood — cannot starve the others or inflate their latency
    past the fairness bound. A tenant's *share* (max concurrent slots, set
    from its vSlice size by the serving gateway) caps how many engine
    slots it may occupy at once — slice-aware scheduling on a shared
    device.

    Two cache layouts:

    * dense (default): per-slot (n_slots, max_len) KV rows, capacity fixed
      at construction;
    * ``paged=True``: one shared page pool (``cache_pages`` pages of
      ``page_size`` positions) virtualized across slots by block tables.
      Admission allocates pages (and *defers* — queues — when the pool or
      the tenant's page budget is exhausted, instead of OOMing), slots
      grow page-by-page as decoding proceeds, and requests of one tenant
      with a common prompt prefix share refcounted pages copy-on-write.
      A slot that cannot grow is preempted back to the queue head (its
      generated tokens survive via prompt-prefix replay).

    Greedy decoding (argmax) — deterministic, testable.
    """

    # contexts shorter than this prefill through the (already compiled)
    # decode program; longer ones get the batched prefill call
    PREFILL_MIN_TOKENS = 4

    def __init__(self, model: Model, params, n_slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 prefill_mode: str = "batched",
                 id_counter: Optional[Iterator[int]] = None,
                 paged: bool = False, page_size: int = 16,
                 cache_pages: Optional[int] = None,
                 scrub_on_free: bool = True,
                 device: Optional[jax.Device] = None):
        # Slot recycling relies on position-masked KV caches (stale entries
        # carry positions > current and are masked out). SSM state has no
        # such masking, so the engine serves attention-family models; SSM
        # serving uses jit_serve_step directly with per-batch state resets.
        if model.cfg.ssm is not None:
            raise ValueError("BatchingEngine supports attention-family "
                             "models; use jit_serve_step for SSM archs")
        if prefill_mode not in ("batched", "legacy"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        self.model = model
        # the device this engine's dataplane lives on (None: JAX's
        # default). Its params replica, caches and every host upload are
        # placed there, so jitted steps run on it.
        self.device = device
        self.params = params if device is None \
            else jax.device_put(params, device)
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.prefill_mode = prefill_mode
        self.paged = paged
        self._queues: "Dict[str, Deque[Request]]" = {}
        self._qlock = threading.Lock()
        self._tenant_share: Dict[str, int] = {}      # max concurrent slots
        self._tenant_pages: Dict[str, int] = {}      # max pool pages held
        self._tenant_weight: Dict[str, float] = {}   # fair-share weight
        self._deficit: Dict[str, float] = {}         # DRR credit per tenant
        self._rr_offset = 0                          # DRR tie-break cursor
        # request ids: a fleet passes one shared counter to every engine so
        # ids stay unique across devices (the hypervisor audit log and a
        # live hand-off both key on them)
        self._ids = id_counter if id_counter is not None \
            else itertools.count()
        self._slots: List[Optional[Request]] = [None] * n_slots
        # slots admitted asynchronously whose prefill is still being
        # accounted chunk-by-chunk (event-driven loop only; the lockstep
        # path admits synchronously and never populates this)
        self._prefilling: Dict[int, _PendingPrefill] = {}
        self.steps = 0
        self.preemptions = 0
        self.scrub_dispatches = 0  # batched zero-on-free scrub calls
        self._scope = sanitizer.scope()      # slot-machine key namespace
        # device block-table cache, keyed on the pool's version counter:
        # steady-state decode steps reuse it instead of re-uploading the
        # (n_slots, max_blocks) table every token
        self._bt_cache = None
        self._bt_version = -1
        if paged:
            if model.cfg.mla is not None:
                raise ValueError("paged KV caches support plain-attention "
                                 "models (MLA latents are not paged)")
            if max_len % page_size:
                raise ValueError(f"max_len {max_len} must be a multiple of "
                                 f"page_size {page_size}")
            self.page_size = page_size
            max_blocks = max_len // page_size
            if cache_pages is None:
                cache_pages = default_pool_pages(n_slots, max_blocks)
            self.cache_pages = cache_pages
            self.pool = PagePoolManager(cache_pages, page_size, n_slots,
                                        max_blocks,
                                        scrub_on_free=scrub_on_free)
            self.caches = self._alloc(
                lambda: model.make_paged_caches(cache_pages, page_size))
            self._sweep_layers = paged_sweep_layers(
                model.cfg, self.caches, n_slots, max_blocks)
            self._pos = np.full((n_slots,), -1, np.int32)
            step = make_paged_serve_step(model)
            self._decode = jax.jit(step)
            self._prefill = _prefill_jit(model, max_len, full_len=True)
            self._min_cache_len = max_len      # full-length pools, no ring
        else:
            self.page_size = 0
            self.cache_pages = 0
            self.pool = None
            self.caches = self._alloc(
                lambda: model.make_caches(n_slots, max_len))
            self._pos = np.zeros((n_slots,), np.int32)
            self._decode = jax.jit(
                lambda p, c, t, pos: model.decode(p, c, t, pos))
            # batched slot prefill: model.prefill over the prompt, spliced
            # into this slot's row of the shared caches. Padding a prefill
            # past the shortest layer cache (a local-attention window)
            # would evict real in-window history, so pad buckets are
            # clamped to it.
            self._prefill = _prefill_jit(model, max_len)
            lens = [l.shape[2] for l in jax.tree.leaves(self.caches)
                    if getattr(l, "ndim", 0) >= 3]
            self._min_cache_len = min(lens) if lens else max_len
        self._splice = _splice_slot
        # hooks for the serving gateway: called after every decode step /
        # on every request completion
        self.on_step: Optional[Callable[[Dict[str, int], float], None]] = None
        self.on_finish: Optional[Callable[[Request], None]] = None

    def _put(self, x):
        """Host -> this engine's device (any pytree of arrays)."""
        return jax.device_put(x, self.device)

    def _alloc(self, make):
        """Build a pytree of device arrays directly on this engine's
        device (no staging copy on the default device)."""
        with jax.default_device(self.device):
            return self._put(make())

    def use_program(self, compiled: Callable) -> None:
        """Swap in an externally compiled decode executable — the serving
        gateway routes compilation through the hypervisor's Reconfigurator
        so the decode program lives in the RC3E program cache (and PR swaps
        bind it to each tenant's vSlice)."""
        self._decode = compiled

    def set_tenant_share(self, tenant: str, max_slots: Optional[int]) -> None:
        """Cap a tenant's concurrent engine slots (None removes the cap)."""
        if max_slots is None:
            self._tenant_share.pop(tenant, None)
        else:
            self._tenant_share[tenant] = max(1, int(max_slots))

    def set_tenant_weight(self, tenant: str,
                          weight: Optional[float]) -> None:
        """Fair-share weight for the deficit round-robin admission policy
        (None resets to the default 1.0). A tenant accrues credit in
        proportion to its weight and pays for every admission in
        proportion to the context it prefills — so a hostile tenant
        flooding long prompts buys *fewer* admissions per unit time, not
        more, and a co-tenant's latency stays bounded."""
        if weight is None:
            self._tenant_weight.pop(tenant, None)
        else:
            self._tenant_weight[tenant] = max(1e-3, float(weight))

    def set_tenant_pages(self, tenant: str,
                         max_pages: Optional[int]) -> None:
        """Cap a tenant's pool pages (paged mode; None removes the cap).
        The gateway/fleet set this from the tenant's vSlice ``cache_pages``
        grant and the service model's ``max_cache_pages_per_tenant`` quota;
        a tenant at its cap queues instead of allocating (no OOM)."""
        if max_pages is None:
            self._tenant_pages.pop(tenant, None)
        else:
            self._tenant_pages[tenant] = max(1, int(max_pages))

    def submit(self, prompt, max_new_tokens: int = 16,
               tenant: str = "default") -> Request:
        prompt = np.asarray(prompt, np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt: a request needs at least one "
                             "prompt token to seed decoding")
        if self.paged:
            worst = (len(prompt) + max_new_tokens - 1) // self.page_size + 1
            if worst > self.pool.max_blocks:
                raise ValueError(
                    f"request may need {worst} blocks, block table has "
                    f"{self.pool.max_blocks} (max_len {self.max_len}) — "
                    "it could never be admitted")
            if worst > self.pool.total_pages:
                raise ValueError(
                    f"request may need {worst} pages, pool has only "
                    f"{self.pool.total_pages} — it could never be admitted")
        req = Request(next(self._ids), prompt, max_new_tokens, tenant=tenant)
        if sanitizer.enabled:
            req._san = sanitizer.scope()
            _req_event(req, "submit")
        with self._qlock:
            self._queues.setdefault(tenant,
                                    collections.deque()).append(req)
        return req

    def resume(self, req: Request, front: bool = False) -> Request:
        """Requeue a request drained from another engine (live migration)
        or preempted locally: its already-generated tokens are preserved
        and replayed as a prompt prefix when the request is re-admitted
        (see ``_admit``). ``front`` preserves FIFO order for preemption.

        A request cancelled while in transit between engines (drained for
        a hand-off but not yet resumed, or orphaned by a dead device) is
        already settled — requeuing it would decode a finished request and
        settle its quota twice, so it is dropped here."""
        if req.done.is_set():
            return req
        _req_event(req, "requeue")
        with self._qlock:
            q = self._queues.setdefault(req.tenant, collections.deque())
            if front:
                q.appendleft(req)
            else:
                q.append(req)
        return req

    # ---------------- tenant bookkeeping ----------------
    def _drain_queue(self, tenant: str) -> List[Request]:
        """Remove and return all of a tenant's queued requests."""
        with self._qlock:
            q = self._queues.pop(tenant, None)
        return list(q) if q is not None else []

    def cancel_queued(self, tenant: str) -> List[Request]:
        """Drop a tenant's not-yet-admitted requests (e.g. its serving
        session closed). Returns the cancelled requests, marked done."""
        dropped = self._drain_queue(tenant)
        for r in dropped:
            _req_event(r, "cancel")
            r.finish_reason = "cancelled"
            r.finished_at = time.monotonic()
            r.done.set()
        return dropped

    def cancel(self, req: Request) -> bool:
        """Cancel ONE request wherever it is: still queued (dropped from
        its tenant queue) or in flight (its slot — and, in paged mode, its
        pool pages — are freed immediately instead of burning until
        ``max_new_tokens``). Fires ``on_finish`` so the gateway settles the
        quota. Returns False when the request already finished."""
        if req.done.is_set():
            return False
        dequeued = False
        with self._qlock:
            q = self._queues.get(req.tenant)
            if q is not None and req in q:
                q.remove(req)
                if not q:
                    del self._queues[req.tenant]
                dequeued = True
        if dequeued:
            self._finish(req, "cancelled")
            return True
        for i, r in enumerate(self._slots):
            if r is req:
                self._release_slot(i)
                self._finish(req, "cancelled")
                return True
        return False

    def _finish(self, req: Request, reason: str):
        _req_event(req, "cancel" if reason == "cancelled" else "finish")
        req.finish_reason = reason
        req.finished_at = time.monotonic()
        req.done.set()
        if self.on_finish is not None:
            self.on_finish(req)

    def _release_slot(self, slot: int):
        """Free a slot (and its pool pages) without touching the request."""
        sanitizer.emit("slot", (self._scope, slot), "release")
        self._slots[slot] = None
        self._prefilling.pop(slot, None)   # buffered prefill dies with it
        self._pos[slot] = -1 if self.paged else 0
        if self.paged:
            self.pool.release_slot(slot)

    def drain_tenant(self, tenant: str) -> List[Request]:
        """Evict a tenant's in-flight and queued requests for live hand-off
        to another engine. In-flight requests keep their generated tokens
        (``resume`` on the target replays them as a prompt prefix; a paged
        fleet copies their pages instead — export BEFORE draining); nothing
        is marked done. Freed slots' stale cache rows stay position-masked
        until recycled. Returns the requests, in-flight first."""
        moved: List[Request] = []
        for i, r in enumerate(self._slots):
            if r is not None and r.tenant == tenant:
                _req_event(r, "drain")
                self._release_slot(i)
                moved.append(r)
        moved.extend(self._drain_queue(tenant))
        return moved

    def inflight(self, tenant: Optional[str] = None) -> List[Request]:
        """Requests currently holding a slot (optionally one tenant's)."""
        return [r for r in self._slots
                if r is not None and (tenant is None or r.tenant == tenant)]

    def holds(self, req: Request) -> bool:
        """Is this request physically on this engine (slotted or queued)?
        The failover sweep consults it: an overlapped hand-off's source
        keeps decoding a migrating tenant's requests while the page copy
        is in flight, and if the tenant's TARGET device dies in that
        window, recovery must not replay requests a live engine still
        owns (double-decode)."""
        if any(r is req for r in self._slots):
            return True
        with self._qlock:
            q = self._queues.get(req.tenant)
            return q is not None and any(r is req for r in q)

    def active_by_tenant(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for r in self._slots:
            if r is not None:
                counts[r.tenant] = counts.get(r.tenant, 0) + 1
        return counts

    def queued_by_tenant(self) -> Dict[str, int]:
        """Queue depth per tenant. Tenant keys live only while a queue is
        non-empty (emptied queues are pruned at pop/drain time), so tenant
        churn cannot grow this map — or the admission round-robin —
        unboundedly."""
        with self._qlock:
            return {t: len(q) for t, q in self._queues.items() if q}

    def _ctx_tokens(self, req: Request) -> np.ndarray:
        """Prompt + already-generated tokens: the context a (re-)admission
        must cover (the final token seeds the next decode step)."""
        if not req.out_tokens:
            return req.prompt
        # admission-time list->array conversion, not per-decode-step
        return np.concatenate(
            [req.prompt,
             np.asarray(req.out_tokens, np.int32)])  # rc3e: allow-host-sync

    def _invalidate_pages(self, pages) -> None:
        """Scrub recycled pages' stale ``pos`` metadata before first use.
        Callers that overwrite a whole page (batched splice, page import,
        COW copy) skip this; token-at-a-time writers (legacy prefill,
        decode into a freshly grown page) must not leave the previous
        occupant's positions masquerading as their own history."""
        if not self.paged or not pages:
            return
        self.caches = _invalidate_pool_pages(
            self.caches,
            self._put(np.asarray(sorted(pages),      # rc3e: allow-host-sync
                                 np.int32)))

    def _flush_scrub(self) -> int:
        """Drain the pool's zero-on-free queue with ONE batched jitted
        zeroing. Called at the top of every step and again immediately
        before any page allocation (grow/COW/admit/import) — a freed page
        must be scrubbed before it can be handed to the next tenant, and
        ``PagePoolManager._alloc_one`` asserts we never miss a site.
        No-op (one int compare) when nothing is pending."""
        if not self.paged or not self.pool.scrub_pending:
            return 0
        pids = self.pool.take_scrub()
        with span("rc3e.engine.scrub", pages=len(pids)):
            self.caches = _scrub_pool_pages(
                self.caches,
                self._put(np.asarray(sorted(pids),   # rc3e: allow-host-sync
                                     np.int32)))
        self.scrub_dispatches += 1
        return len(pids)

    def _page_budget_ok(self, tenant: str, extra: int) -> bool:
        budget = self._tenant_pages.get(tenant)
        return budget is None or \
            self.pool.tenant_pages(tenant) + extra <= budget

    def _can_admit(self, req: Request) -> bool:
        """Paged admission gate: queue-on-exhaustion. A request stays at
        its tenant's queue head until the pool has pages for it AND the
        tenant is under its page budget."""
        if not self.paged:
            return True
        needed = self.pool.pages_needed(
            req.tenant, self._ctx_tokens(req),
            share=self.prefill_mode == "batched")
        return needed <= self.pool.free_pages and \
            self._page_budget_ok(req.tenant, needed)

    def _admit_cost(self, req: Request) -> float:
        """What one admission debits from its tenant's fair-share credit:
        one decode slot plus the prefill work, in page-sized chunks. A
        4-page prompt costs ~5x a one-token resubmit, which is exactly the
        asymmetry a prompt-flood attack exploits under plain round-robin
        (every admission costs 1 there, regardless of prefill length)."""
        unit = self.page_size if self.paged else 16
        return 1.0 + (len(self._ctx_tokens(req)) - 1) / max(1, unit)

    def _pop_next_request(self) -> Optional[Request]:
        """Weighted deficit round-robin over tenants (the per-tenant
        fair-share policy): every *eligible* tenant — spare slot share
        and, in paged mode, an admissible head request — accrues credit
        proportional to its weight each time a slot is offered, the
        highest-credit tenant is served, and the admission debits its
        credit by ``_admit_cost`` (slot + prefill chunks). Ties break in
        rotation order after the last served tenant, so equal-weight
        tenants degenerate to the old round-robin. Blocked tenants accrue
        nothing (a page-starved head must not bank unbounded priority),
        and credit is pruned with the tenant's last queued request so
        tenant churn cannot grow the map. Emptied queues are pruned here
        so long-gone tenants don't linger in the rotation."""
        with self._qlock:
            active = self.active_by_tenant()
            # prune credit/debt only once a tenant is fully gone (no queue,
            # no slots): clearing debt while it still holds slots would let
            # a one-request-at-a-time flood dodge its admission debits
            for t in list(self._deficit):
                if t not in self._queues and not active.get(t):
                    del self._deficit[t]
            tenants = [t for t, q in self._queues.items() if q]
            if not tenants:
                return None
            n = len(tenants)
            order = [tenants[(self._rr_offset + k) % n] for k in range(n)]
            eligible = []
            for t in order:
                share = self._tenant_share.get(t, self.n_slots)
                if active.get(t, 0) >= share:
                    continue
                if not self._can_admit(self._queues[t][0]):
                    continue        # per-tenant FIFO: head blocks the rest
                eligible.append(t)
            if not eligible:
                return None
            best = None
            for t in eligible:
                self._deficit[t] = self._deficit.get(t, 0.0) + \
                    self._tenant_weight.get(t, 1.0)
                if best is None or self._deficit[t] > self._deficit[best]:
                    best = t        # strict >: first-in-order wins ties
            req = self._queues[best].popleft()
            if not self._queues[best]:
                del self._queues[best]
            self._deficit[best] = self._deficit.get(best, 0.0) - \
                self._admit_cost(req)
            self._rr_offset = (tenants.index(best) + 1) % n
            return req

    # ---------------- engine loop ----------------
    def _admit(self, async_chunk: Optional[int] = None):
        for slot in range(self.n_slots):
            if self._slots[slot] is not None:
                continue
            with span("rc3e.engine.pick"):
                req = self._pop_next_request()
            if req is None:
                return
            if req.admitted_at is None:
                req.admitted_at = time.monotonic()
            with span("rc3e.engine.admit", request=req.request_id):
                self._admit_one(slot, req, async_chunk)

    def _admit_one(self, slot: int, req: Request,
                   async_chunk: Optional[int]):
        """Seat ``req`` in the free ``slot`` and prefill its context."""
        self._slots[slot] = req
        sanitizer.emit("slot", (self._scope, slot), "occupy")
        _req_event(req, "admit")
        if async_chunk is not None:
            # event-driven admission: buffer the prefill and account it
            # async_chunk tokens per engine event (see step_async)
            self._start_prefill_async(slot, req, async_chunk)
            return
        # a request resumed after live migration replays prompt +
        # already-generated tokens so decode continues where it left off
        toks = self._ctx_tokens(req)
        if self.paged:
            self._admit_paged(slot, req, toks)
        else:
            ctx = toks[:-1]
            if len(ctx) >= self.PREFILL_MIN_TOKENS \
                    and self.prefill_mode == "batched":
                self._prefill_slot(slot, req, ctx)
            else:
                # short context (or legacy mode): feed tokens through the
                # already-compiled decode program, slot-isolated
                for i, t in enumerate(ctx):
                    self._step_single(slot, int(t), i)
            self._pos[slot] = len(toks) - 1
        req._next_input = int(toks[-1])
        _req_event(req, "ready")   # lockstep: prefill completed inline

    def _start_prefill_async(self, slot: int, req: Request, chunk: int):
        """Admit ``req`` into ``slot`` without blocking the engine event:
        compute the batched prefill once, buffer the result, and hand the
        slot to ``step_async`` to account one ``chunk`` of context tokens
        per event before it joins decode. Contexts the lockstep path
        already handles synchronously (short, legacy-mode, or fully
        prefix-matched paged admissions) stay synchronous — they are
        O(chunk) work anyway — and become ready within this event."""
        toks = self._ctx_tokens(req)
        ctx = toks[:-1]
        plan = None
        if self.paged:
            self._flush_scrub()
            plan = self.pool.admit(slot, req.tenant, toks,
                                   share=self.prefill_mode == "batched")
        buf = None
        chunks = 0
        if plan is not None and plan.skip_prefill:
            pass                        # every context page prefix-matched
        elif len(ctx) >= self.PREFILL_MIN_TOKENS \
                and self.prefill_mode == "batched":
            buf = self._run_prefill(req, ctx)
            chunks = -(-len(ctx) // max(1, int(chunk)))   # ceil
        else:
            if plan is not None:
                self._invalidate_pages(plan.write_pages)
            for i, t in enumerate(ctx):
                self._step_single(slot, int(t), i)
        if self.paged:
            # masked until ready: decode rows at -1 write the null page,
            # and _prepare_writes skips the slot entirely
            self._pos[slot] = -1
        pending = _PendingPrefill(chunks, buf, plan, len(toks),
                                  int(toks[-1]))
        if chunks <= 0:
            self._finish_prefill(slot, pending)
        else:
            self._prefilling[slot] = pending

    def _finish_prefill(self, slot: int, pending: _PendingPrefill):
        """Splice the buffered prefill and open the slot for decode."""
        req = self._slots[slot]
        if pending.buf is not None:
            if self.paged:
                self._splice_paged(pending.buf, pending.plan)
            else:
                with span("rc3e.engine.splice"):
                    self.caches = self._splice(self.caches, pending.buf,
                                               slot)
        self._pos[slot] = pending.ctx_len - 1
        req._next_input = pending.last_token
        _req_event(req, "ready")

    def _admit_paged(self, slot: int, req: Request, toks: np.ndarray):
        """Page-granular admission: prefix-matched pages are adopted by
        refcount; only the unshared suffix blocks are prefilled + spliced.
        Legacy prefill steps every context token through the decode program
        (writes at every position), so it must not adopt shared pages."""
        self._flush_scrub()
        plan = self.pool.admit(slot, req.tenant, toks,
                               share=self.prefill_mode == "batched")
        ctx = toks[:-1]
        if not plan.skip_prefill:
            if len(ctx) >= self.PREFILL_MIN_TOKENS \
                    and self.prefill_mode == "batched":
                self._prefill_slot_paged(slot, req, ctx, plan)
            else:
                self._invalidate_pages(plan.write_pages)
                for i, t in enumerate(ctx):
                    self._step_single(slot, int(t), i)
        self._pos[slot] = len(toks) - 1

    def _prefill_slot(self, slot: int, req: Request, ctx: np.ndarray):
        """Prefill a slot's context with ONE batched call instead of one
        full-batch decode per prompt token (O(S·n_slots) -> O(S) work,
        O(1) dispatches). Lengths are padded to power-of-two buckets to
        bound recompiles; padded positions carry pos >= len(ctx), so they
        are causally masked during decode and overwritten in place when
        generation reaches them."""
        slot_caches = self._run_prefill(req, ctx)
        with span("rc3e.engine.splice"):
            self.caches = self._splice(self.caches, slot_caches, slot)

    def _prefill_slot_paged(self, slot: int, req: Request, ctx: np.ndarray,
                            plan):
        """Prefill, then scatter ONLY the unshared suffix blocks into this
        slot's pool pages (shared prefix pages already hold identical
        content — that's the point of sharing them)."""
        self._splice_paged(self._run_prefill(req, ctx), plan)

    def _run_prefill(self, req: Request, ctx: np.ndarray):
        """Dispatch the batched prefill of ``ctx``; returns its batch-1
        caches. The span's ``tokens`` are the real context tokens,
        ``padded`` the bucket ``_pad_ctx`` chose."""
        toks = self._pad_ctx(ctx)
        with span("rc3e.engine.prefill", request=req.request_id,
                  tokens=len(ctx), padded=toks.shape[1]):
            _, slot_caches = self._prefill(self.params, toks)
        return slot_caches

    def _splice_paged(self, slot_caches, plan) -> None:
        """Scatter a batch-1 prefill into the plan's write pages."""
        with span("rc3e.engine.splice"):
            # admission-time upload of the write-page index vector
            pages = self._put(
                np.asarray(plan.write_pages,         # rc3e: allow-host-sync
                           np.int32))
            self.caches = _splice_pages(self.caches, slot_caches, pages,
                                        start=plan.write_start)

    def _pad_ctx(self, ctx: np.ndarray):
        n = len(ctx)
        bucket = 8
        while bucket < n:
            bucket *= 2
        pad = max(n, min(bucket, self._min_cache_len))
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n] = ctx
        # prefill prompt upload: once per admission, not per step
        return self._put(toks)

    def _block_tables_dev(self):
        """Device copy of the pool block tables, re-uploaded only when the
        pool's ``version`` counter moved (bumped on every admit/grow/cow/
        release). Steady-state decode steps — no admission, no growth —
        reuse the cached array instead of paying an H2D transfer of the
        whole (n_slots, max_blocks) table per generated token."""
        if self._bt_version != self.pool.version:
            self._bt_cache = self._put(self.pool.block_tables)
            self._bt_version = self.pool.version
        return self._bt_cache

    def _sweep_counts(self) -> Dict[str, int]:
        """Block-table columns the paged decode sweep covers at the
        current positions, and the table's columns, each summed over the
        paged attention layers: the same bound the device computes."""
        nb = self.pool.block_tables.shape[1]
        swept = total = 0
        for window, chunk, layers in self._sweep_layers:
            swept += layers * swept_cols(self._pos, self.page_size, nb,
                                         window, chunk)
            total += layers * nb
        return {"table_cols_swept": swept, "table_cols": total}

    def _step_single(self, slot: int, token: int, pos: int):
        """Replay ONE context token through the decode program (short or
        legacy-mode prefill). The logits are deliberately dropped on
        device — only the cache writes matter here."""
        tokens = np.zeros((self.n_slots, 1), np.int32)
        tokens[slot, 0] = token
        if self.paged:
            # other rows stay inactive (-1): their k/v writes land in the
            # null page instead of garbling a possibly-shared write page
            posv = np.full((self.n_slots,), -1, np.int32)
            posv[slot] = pos
            _, self.caches = self._decode(
                self.params, self.caches, self._put(tokens),
                self._put(posv), self._block_tables_dev())
        else:
            posv = self._pos.copy()
            posv[slot] = pos
            _, self.caches = self._decode(
                self.params, self.caches, self._put(tokens),
                self._put(posv))

    def _prepare_writes(self) -> Dict[str, int]:
        """Before a paged decode step: every active slot's write position
        must land in a privately owned page. Crossing a page boundary
        grows the slot by one page; a shared (prefix) page is detached
        copy-on-write; exhaustion preempts the slot back to its queue head
        (generated tokens survive via prefix replay). Returns how many
        slots this sweep grew, detached and preempted."""
        ps = self.page_size
        n = {"grown": 0, "cow": 0, "preempted": 0}
        for i, req in enumerate(self._slots):
            if req is None or i in self._prefilling:
                continue            # mid-prefill: pos is -1, nothing writes
            wpos = int(self._pos[i])
            block = wpos // ps
            if block >= len(self.pool.slot_blocks(i)):
                if self.pool.free_pages >= 1 and \
                        self._page_budget_ok(req.tenant, 1):
                    # an earlier slot in this same sweep may have been
                    # preempted — its pages must be scrubbed before they
                    # can be regrown here
                    self._flush_scrub()
                    self._invalidate_pages([self.pool.grow(i, req.tenant)])
                    n["grown"] += 1
                else:
                    self._preempt(i)
                    n["preempted"] += 1
                continue
            if self.pool.is_shared(i, block):
                if self.pool.free_pages >= 1 and \
                        self._page_budget_ok(req.tenant, 1):
                    self._flush_scrub()
                    src, dst = self.pool.cow(i, block, req.tenant)
                    self.caches = _copy_page(self.caches, np.int32(src),
                                             np.int32(dst))
                    n["cow"] += 1
                else:
                    self._preempt(i)
                    n["preempted"] += 1
                continue
            self.pool.touch_write(i, block)
        return n

    def _preempt(self, slot: int):
        req = self._slots[slot]
        _req_event(req, "preempt")
        self._release_slot(slot)
        self.resume(req, front=True)
        self.preemptions += 1

    def step(self) -> int:
        """One engine iteration: admit + one decode step for active slots.
        Returns number of active slots."""
        self._flush_scrub()       # pages freed since the last step
        self._admit()
        return self._decode_once()

    def step_async(self, prefill_chunk: int = 4) -> int:
        """One EVENT-DRIVEN engine iteration: admit without blocking
        (prefills are buffered and accounted ``prefill_chunk`` context
        tokens per event), advance pending prefills one chunk, then decode
        the slots whose prefill already completed. Prefill no longer
        stalls co-resident tenants' decode — the overlap the lockstep
        ``step()`` cannot express. Token streams are bit-identical to the
        lockstep path: the same prefill result is spliced (just later) and
        greedy per-slot decoding is schedule-independent."""
        self._flush_scrub()       # pages freed since the last event
        self._admit(async_chunk=prefill_chunk)
        for slot in sorted(self._prefilling):
            pending = self._prefilling[slot]
            pending.chunks_left -= 1
            _req_event(self._slots[slot], "chunk")
            if pending.chunks_left <= 0:
                del self._prefilling[slot]
                self._finish_prefill(slot, pending)
        return self._decode_once()

    def _decode_once(self) -> int:
        """One decode step over every ready slot (mid-prefill slots are
        excluded). Returns the number of slots decoded."""
        if self.paged:
            with span("rc3e.engine.prepare_writes") as sp:
                sp.set(**self._prepare_writes())
        active = [i for i, r in enumerate(self._slots)
                  if r is not None and i not in self._prefilling]
        if not active:
            return 0
        with span("rc3e.engine.decode_dispatch") as sp:
            if sp and self.paged:
                sp.set(**self._sweep_counts())
            tokens = np.zeros((self.n_slots, 1), np.int32)
            for i in active:
                tokens[i, 0] = self._slots[i]._next_input
            t0 = time.monotonic()
            # the two small per-step uploads ((n_slots, 1) tokens and
            # (n_slots,) positions) are the step's inputs — unavoidable
            # and tiny; the block tables come from the version-keyed cache
            if self.paged:
                logits, self.caches = self._decode(
                    self.params, self.caches, self._put(tokens),
                    self._put(self._pos), self._block_tables_dev())
            else:
                logits, self.caches = self._decode(
                    self.params, self.caches, self._put(tokens),
                    self._put(self._pos))
            # argmax on device: fetch (n_slots,) int32 ids, not the full
            # (n_slots, 1, vocab) logits tensor
            ids = _argmax_tokens(logits)
        with span("rc3e.engine.readback"):
            next_ids = np.asarray(ids)               # rc3e: allow-host-sync
            step_ms = (time.monotonic() - t0) * 1e3
        self.steps += 1
        with span("rc3e.engine.emit"):
            if self.on_step is not None:
                self.on_step(self.active_by_tenant(), step_ms)
            for i in active:
                req = self._slots[i]
                nxt = int(next_ids[i])
                if req.first_token_at is None:
                    req.first_token_at = time.monotonic()
                req.out_tokens.append(nxt)
                req._next_input = nxt
                self._pos[i] += 1
                eos = self.eos_id is not None and nxt == self.eos_id
                if len(req.out_tokens) >= req.max_new_tokens or eos \
                        or self._pos[i] >= self.max_len - 1:
                    self._release_slot(i)
                    self._finish(req, "eos" if eos else "length")
        return len(active)

    def idle(self) -> bool:
        with self._qlock:
            queued = any(self._queues.values())
        return all(r is None for r in self._slots) and not queued

    def run_until_idle(self, max_steps: int = 10000) -> bool:
        """Run until no work remains. Returns True when fully drained,
        False when ``max_steps`` expired with work still pending OR queued
        work can make no progress (e.g. page-budget starvation with
        nothing in flight) — callers must not mistake a stall for
        completion."""
        for _ in range(max_steps):
            n = self.step()
            if self.idle():
                return True
            if n == 0:
                return False        # nothing active, nothing admittable
        return self.idle()

    # ---------------- paged introspection / hand-off ----------------
    def page_stats(self) -> dict:
        """Pool occupancy for the monitor (empty dict in dense mode)."""
        if not self.paged:
            return {}
        s = self.pool.stats()
        s["preemptions"] = self.preemptions
        s["scrub_dispatches"] = self.scrub_dispatches
        return s

    def export_request_pages(self, req: Request):
        """Gather an in-flight request's pool pages to host memory for a
        live hand-off (leaves (L, nb, ps, ...)). Call BEFORE draining —
        released pages may be recycled by the next admission. Returns None
        when the request holds no slot or the engine is dense."""
        if not self.paged:
            return None
        for i, r in enumerate(self._slots):
            if r is req:
                pages = self.pool.slot_blocks(i)
                if not pages:
                    return None
                idx = np.asarray(pages, np.int32)
                return jax.tree.map(lambda a: np.asarray(a[:, idx]),
                                    self.caches)
        return None

    def import_request_pages(self, req: Request, payload,
                             ctx_len: Optional[int] = None) -> bool:
        """Adopt a migrated request by copying its pages into this pool —
        decode continues WITHOUT prefix replay. Returns False (caller
        falls back to replay) when no slot, pages or budget are free.

        ``ctx_len`` is the request's context length AT EXPORT TIME. The
        overlapped hand-off keeps decoding on the source while the page
        copy is in flight, so by adoption time the request may hold a few
        tokens the snapshot doesn't cover; those positions
        (``ctx_len-1 .. now-2``) are caught up by replaying just the delta
        through the decode program — pages grown as needed — instead of
        replaying the whole prefix. ``None`` means the snapshot is
        current (the lockstep hand-off exports and drains atomically)."""
        if not self.paged:
            return False
        # geometry guard: a cross-class hand-off can land a snapshot cut
        # at the SOURCE pool's page size on a pool tuned to a different
        # one — the pages cannot be adopted page-for-page, so decline and
        # let the caller fall back to prefix replay (bit-exact greedy)
        if jax.tree.leaves(payload)[0].shape[2] != self.page_size:
            return False
        slot = next((i for i, r in enumerate(self._slots) if r is None),
                    None)
        if slot is None:
            return False
        nb = jax.tree.leaves(payload)[0].shape[1]
        if nb > self.pool.free_pages or \
                not self._page_budget_ok(req.tenant, nb):
            return False
        self._flush_scrub()
        pages = [self.pool.grow(slot, req.tenant) for _ in range(nb)]
        self.caches = _import_pages(
            self.caches, self._put(payload),
            self._put(np.asarray(pages, np.int32)))
        toks = self._ctx_tokens(req)
        base = len(toks) if ctx_len is None else int(ctx_len)
        # catch-up: KV for positions 0..base-2 arrived with the snapshot;
        # anything the source generated after the export is replayed here
        for off, t in enumerate(toks[base - 1:len(toks) - 1]):
            pos = base - 1 + off
            if pos // self.page_size >= len(self.pool.slot_blocks(slot)):
                if self.pool.free_pages >= 1 and \
                        self._page_budget_ok(req.tenant, 1):
                    self._flush_scrub()
                    self._invalidate_pages(
                        [self.pool.grow(slot, req.tenant)])
                else:
                    # can't cover the delta — roll the adoption back and
                    # let the caller fall back to prefix replay
                    self.pool.release_slot(slot)
                    return False
            self._step_single(slot, int(t), pos)
        self._slots[slot] = req
        sanitizer.emit("slot", (self._scope, slot), "occupy")
        _req_event(req, "adopt")
        self._pos[slot] = len(toks) - 1
        req._next_input = int(toks[-1])
        return True
