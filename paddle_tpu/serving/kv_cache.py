"""Paged KV cache — the decode engine's preallocated page pool.

vLLM's PagedAttention memory discipline in dense-jax form: instead of
one max-length KV buffer per request (whose worst case is what forces
tiny batch sizes), the engine preallocates ONE pool of fixed-size pages
per layer and hands each request just the pages its sequence actually
needs. Pages are allocated at admission (worst case for the request:
ceil((prompt + max_new_tokens) / page_size), so a mid-generation
allocation can never fail) and freed the moment the request retires —
continuous batching churns requests through the same arrays with no
device alloc/free traffic at all.

Page 0 is a reserved scratch page: the ops route padded prompt
positions and empty decode slots there (see ops/attention_ops.py
kv_cache_write / cached_kv_attention), so a masked write can never
touch a page owned by a live request.

Accounting: the pool's bytes book into the PR 10 HBM ledger as
``mem.serving.kv_pool_bytes`` (preallocated, the resident figure),
``mem.serving.kv_used_bytes`` (pages currently owned by live requests)
and ``mem.serving.kv_high_water_bytes`` — rendered by tools/mem_report
and /v1/stats, and what lets admission refuse a request that would OOM
(typed ``KVCacheExhaustedError``) instead of dying mid-decode.
``decode.kv_alloc`` is a fault-injection site (core/faults.py,
tools/chaos_check.py --decode).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from ..core import costmodel, faults, telemetry
from ..core.analysis import lockdep
from .admission import KVCacheExhaustedError


@dataclass(frozen=True)
class LayerCache:
    """What one layer keeps of a sequence: K and V of `kv_dim` a token, in
    a context's pages (`window` 0: every token of the request, pages held
    for its whole life) or in a ring (`window` > 0: the last `window`
    tokens, in a fixed ring of ``window / page + 1`` pages a slot that the
    sliding window overwrites). A `latent` layer keeps ONE array of
    `kv_dim` a token in a context's pages (multi-head latent attention:
    the normed compressed latent, then the rotated key all heads share),
    from which every head's key and value are expanded or absorbed; it has
    no separate V.

    Beside its pages a layer may keep a per-SLOT state (a state-space or
    linear-attention mixer: a third kind of per-request state, a fixed size
    a request, not a size a token): `ssm_state` = (heads, d_state,
    head_dim), the axes in the order the arrays hold them (d_state, a
    delta rule's key axis, on sublanes; head_dim, its value axis, on
    lanes), the recurrent state in `state_dtype`; and `conv_tail` =
    (d_conv - 1, conv_dim), the convolution's last inputs, time-major, in
    the pages' dtype. A prefill WRITES the request's slot of both, a step
    advances them in place.

    A STATE-ONLY layer (`kv_dim` 0 with a state: a linear-attention layer
    of a model whose other layers attend) keeps no K or V at all: it is in
    no class of pages, no pool array is made for it, and its bytes are the
    state class's alone. Its state may be a TAIL ALONE (`conv_tail` set,
    no `ssm_state`: a layer whose only mixer is a gated short convolution,
    models/lfm2.py): then no recurrent-state array exists for it either,
    no state kernel runs, and its bytes are the tail's."""
    kv_dim: int
    window: int = 0
    latent: bool = False
    ssm_state: Tuple[int, ...] = ()
    conv_tail: Tuple[int, ...] = ()
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.kv_dim == 0 and (self.window or self.latent or not (
                self.ssm_state or self.conv_tail)):
            raise ValueError("a layer without K/V (kv_dim 0) is a "
                             "state-only layer: a recurrent state or a "
                             "conv tail, no window, no latent")

    @property
    def ring(self) -> bool:
        return self.window > 0

    @property
    def state_only(self) -> bool:
        return self.kv_dim == 0

    @property
    def tail_only(self) -> bool:
        """In the state class with a conv tail and no recurrent state."""
        return bool(self.conv_tail) and not self.ssm_state


def pool_array_names(layer: int, latent: bool) -> Tuple[str, ...]:
    """The pool arrays of layer `layer`, as the programs feed them (each
    is written back as ``<name>_out``)."""
    return (f"kv_c_{layer}",) if latent \
        else (f"kv_k_{layer}", f"kv_v_{layer}")


def state_array_names(layer: int, tail_only: bool = False
                      ) -> Tuple[str, ...]:
    """The per-slot state arrays of layer `layer` (written back as
    ``<name>_out``): the recurrent state [slots + 1, heads, d_state,
    head_dim] (d_state on sublanes, head_dim on lanes) and the conv tail
    [slots + 1, d_conv - 1, conv_dim] (time-major); the last slot is the
    scratch slot of padding rows and warm-up feeds. A `tail_only` layer
    (`LayerCache.tail_only`) has the tail alone."""
    tail = f"conv_tail_{layer}"
    return (tail,) if tail_only else (f"ssm_state_{layer}", tail)


def ring_pages_per_slot(window: int, page_size: int) -> int:
    """Pages of a slot's ring: the window, and one page more, so that the
    page being written never holds a key the window still reaches."""
    return -(-int(window) // int(page_size)) + 1


class KVPagePool:
    """Free-list allocator over preallocated per-layer page arrays.

    The jax arrays themselves (``pools``: kv_k_<l>/kv_v_<l>, or the one
    kv_c_<l> of a latent layer -> [num_pages, page_size, kv_dim]) are
    owned and threaded/donated by the engine's step function; this object
    owns the PAGE IDS and the ledger accounting. Page 0 is never handed
    out.

    One pool is one CLASS of pages: every layer of it shares the page ids
    (a request's page j is page j of each of the pool's layers). A model
    whose layers all hold a context's pages has one pool over layers
    0..n_layers-1, as ever; `PagedKVCache` below puts a second pool beside
    it for the layers that keep a ring (K and V, or a latent ring's one
    array). ``layers`` names the model's layer
    indices this pool holds (the arrays' names) and ``kv_dims`` their
    widths; ``latent`` says which of them keep one array a layer and not
    K and V (their bytes are also booked as
    ``mem.serving.kv_pool_bytes.latent``); ``klass`` names the class in
    the ledger (``mem.serving.kv_pool_bytes.<klass>``) when there is more
    than one."""

    def __init__(self, n_layers: int, num_pages: int, page_size: int,
                 kv_dim: int, dtype: str = "float32",
                 layers: Optional[List[int]] = None,
                 kv_dims: Optional[List[int]] = None, klass: str = "",
                 latent: Optional[List[bool]] = None):
        if num_pages < 2:
            raise ValueError(f"KV pool needs >= 2 pages (page 0 is the "
                             f"reserved scratch page), got {num_pages}")
        self.n_layers = int(n_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.kv_dim = int(kv_dim)
        self.dtype = dtype
        self.layers = list(range(self.n_layers)) if layers is None \
            else [int(i) for i in layers]
        self.kv_dims = [self.kv_dim] * self.n_layers if kv_dims is None \
            else [int(d) for d in kv_dims]
        self.latent = [False] * self.n_layers if latent is None \
            else [bool(v) for v in latent]
        self.klass = klass
        self._lock = lockdep.lock("serving.kv_pool")
        self._free: List[int] = list(range(1, self.num_pages))
        self._lent: set = set()
        self._high_water_pages = 0
        import numpy as np

        token_bytes = np.dtype(dtype).itemsize * self.page_size \
            * self.num_pages
        # keys + values of every layer; a latent layer keeps one array
        self.latent_bytes = token_bytes * sum(
            d for d, lat in zip(self.kv_dims, self.latent) if lat)
        self.pool_bytes = 2 * token_bytes * sum(self.kv_dims) \
            - self.latent_bytes
        self._page_bytes = self.pool_bytes // self.num_pages
        if self.latent_bytes:
            telemetry.gauge_set("mem.serving.kv_pool_bytes.latent",
                                self.latent_bytes)
        if klass:
            telemetry.gauge_set(f"mem.serving.kv_pool_bytes.{klass}",
                                self.pool_bytes)
        else:
            telemetry.gauge_set("mem.serving.kv_pool_bytes",
                                self.pool_bytes)
            telemetry.gauge_set("mem.serving.kv_used_bytes", 0)
            telemetry.gauge_set("mem.serving.kv_high_water_bytes", 0)
        costmodel.refresh_ledger()

    def array_names(self) -> List[str]:
        """The program feed names of this pool's arrays."""
        return [name for i, lat in zip(self.layers, self.latent)
                for name in pool_array_names(i, lat)]

    def make_arrays(self) -> Dict[str, Any]:
        """Fresh zeroed device pools keyed by the program feed names."""
        import jax.numpy as jnp

        out = {}
        for i, dim, lat in zip(self.layers, self.kv_dims, self.latent):
            shape = (self.num_pages, self.page_size, dim)
            for name in pool_array_names(i, lat):
                out[name] = jnp.zeros(shape, self.dtype)
        return out

    # -- capacity ------------------------------------------------------------
    @property
    def capacity_pages(self) -> int:
        """Allocatable pages (page 0 excluded)."""
        return self.num_pages - 1

    def pages_for_tokens(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)

    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def check_fits(self, tokens: int):
        """Typed admission-time refusal: a request whose WORST-CASE page
        need exceeds the whole pool can never be served — refuse it now
        instead of letting it OOM the cache mid-generation."""
        need = self.pages_for_tokens(tokens)
        if need > self.capacity_pages:
            telemetry.counter_add("decode.kv_refusals", 1, pages=need)
            raise KVCacheExhaustedError(
                f"request needs {need} KV pages ({tokens} tokens at "
                f"{self.page_size}/page) but the pool holds "
                f"{self.capacity_pages} — over the KV budget "
                f"(mem.serving.kv_pool_bytes={self.pool_bytes}); raise "
                f"FLAGS_decode_kv_pages or shorten the request")
        return need

    # -- alloc / free --------------------------------------------------------
    def try_alloc(self, n: int) -> List[int]:
        """Pop n pages, or [] when the pool cannot seat them right now
        (the request stays queued until retirements free pages).
        ``decode.kv_alloc`` faults inject here."""
        faults.maybe_fail("decode.kv_alloc", pages=n)
        with self._lock:
            if n > len(self._free):
                return []
            pages = self._free[:n]
            del self._free[:n]
            self._lent.update(pages)
            used = self.capacity_pages - len(self._free)
            self._high_water_pages = max(self._high_water_pages, used)
            hw = self._high_water_pages
        telemetry.counter_add("decode.kv_pages_allocated", n)
        if not self.klass:
            telemetry.gauge_set("mem.serving.kv_used_bytes",
                                used * self._page_bytes)
            telemetry.gauge_set("mem.serving.kv_high_water_bytes",
                                hw * self._page_bytes)
        return pages

    def free(self, pages: List[int]):
        if not pages:
            return
        with self._lock:
            dup = set(pages) & set(self._free)
            if dup or 0 in pages:
                raise AssertionError(
                    f"KV pool corruption: freeing pages {sorted(dup)} "
                    f"already free (or the reserved page 0)")
            self._free.extend(pages)
            self._lent.difference_update(pages)
            used = self.capacity_pages - len(self._free)
        telemetry.counter_add("decode.kv_pages_freed", len(pages))
        if not self.klass:
            telemetry.gauge_set("mem.serving.kv_used_bytes",
                                used * self._page_bytes)

    # -- invariants ----------------------------------------------------------
    def audit(self, owned: List[int] = None) -> List[str]:
        """Invariant check: the free list and the lent set must PARTITION
        pages 1..num_pages-1 — disjoint, no duplicates, page 0 never
        handed out. With ``owned`` (every page id the callers believe
        they hold: request-private pages + prefix-store pages), also
        checks lent == owned, i.e. no leaked and no over-freed pages.
        Returns a list of violation strings (empty = clean) and counts
        each failing call as ``kv.audit_failures`` — the chaos_check
        --prefix / --decode gate and tests/test_prefix_store.py assert
        on this."""
        problems: List[str] = []
        with self._lock:
            free = list(self._free)
            lent = set(self._lent)
        if len(free) != len(set(free)):
            problems.append("duplicate pages on the free list")
        if 0 in free or 0 in lent:
            problems.append("reserved page 0 entered circulation")
        overlap = set(free) & lent
        if overlap:
            problems.append(f"pages both free and lent: {sorted(overlap)}")
        universe = set(range(1, self.num_pages))
        missing = universe - set(free) - lent
        if missing:
            problems.append(f"pages vanished from the pool: "
                            f"{sorted(missing)}")
        extra = (set(free) | lent) - universe
        if extra:
            problems.append(f"pages outside the pool: {sorted(extra)}")
        if owned is not None:
            owned_set = set(owned)
            if len(owned) != len(owned_set):
                problems.append("a page is owned twice")
            leaked = lent - owned_set
            if leaked:
                problems.append(f"leaked pages (lent but unowned): "
                                f"{sorted(leaked)}")
            stale = owned_set - lent
            if stale:
                problems.append(f"over-freed pages (owned but not "
                                f"lent): {sorted(stale)}")
        if problems:
            telemetry.counter_add("kv.audit_failures", 1)
        return problems

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            free = len(self._free)
            hw = self._high_water_pages
        return {"page_size": self.page_size,
                "pages_total": self.capacity_pages,
                "pages_free": free,
                "pages_used": self.capacity_pages - free,
                "high_water_pages": hw,
                "pool_bytes": self.pool_bytes,
                "used_bytes": (self.capacity_pages - free) *
                self._page_bytes,
                "high_water_bytes": hw * self._page_bytes}


class PagedKVCache:
    """The engine's cache: the owner of one pool a class of pages.

    ``layout`` gives each layer's `LayerCache`. Layers that hold a
    context's pages share the ``context`` pool (page ids, one table row a
    request); layers that keep a ring share the ``ring`` pool, every slot
    a fixed ring of `ring_pages_per_slot` pages. A model without ring
    layers has ``ring is None`` and its context pool is exactly the pool
    it had before there were classes; without ``ring_pages`` the ring
    pool holds one ring for each of ``slots``. A request is seated only if BOTH
    classes can seat it (`try_alloc` takes from both or from neither),
    and `audit` holds both to their invariants.

    Layers with a per-slot state (`LayerCache.ssm_state`, or a
    `conv_tail` alone: ``tail_layers``, of which no recurrent-state array
    is made) add the state
    class, and a state-only layer is in that class alone (the context pool
    holds the arrays of the layers that attend, ``context.layers``): `state_array_names` arrays of ``slots + 1`` states, booked as
    ``mem.serving.state_pool_bytes`` (``.used``: the seated requests'
    share, `note_state_slots`). It has no page ids: a request's state is
    its SLOT's, which the engine hands out with the seat, so a request is
    admitted only where a slot is free, and the slot's state is the
    prefill's to overwrite."""

    CONTEXT, RING = "context", "ring"

    def __init__(self, layout: List[LayerCache], page_size: int,
                 context_pages: int, ring_pages: Optional[int] = None,
                 dtype: str = "float32", slots: int = 0):
        self.layout = list(layout)
        self.page_size = int(page_size)
        ctx = [i for i, lc in enumerate(layout)
               if not lc.ring and not lc.state_only]
        rings = [i for i, lc in enumerate(layout) if lc.ring]
        if not ctx:
            raise ValueError("a model needs at least one layer that holds "
                             "its context's pages")
        windows = {layout[i].window for i in rings}
        if len(windows) > 1:
            raise ValueError(f"one ring class, one window: got {windows}")
        self.window = windows.pop() if windows else 0
        self.ring_slot_pages = ring_pages_per_slot(
            self.window, page_size) if rings else 0
        self.context = KVPagePool(
            len(ctx), context_pages, page_size, layout[ctx[0]].kv_dim,
            dtype, layers=ctx, kv_dims=[layout[i].kv_dim for i in ctx],
            klass=self.CONTEXT if rings else "",
            latent=[layout[i].latent for i in ctx])
        self.ring: Optional[KVPagePool] = None
        if rings:
            if ring_pages is None:     # a ring for every slot, and page 0
                ring_pages = slots * self.ring_slot_pages + 1
            self.ring = KVPagePool(
                len(rings), ring_pages, page_size, layout[rings[0]].kv_dim,
                dtype, layers=rings,
                kv_dims=[layout[i].kv_dim for i in rings], klass=self.RING,
                latent=[layout[i].latent for i in rings])
            telemetry.gauge_set("mem.serving.kv_pool_bytes",
                                self.pool_bytes)
            if self.ring.latent_bytes:      # latent rows of both classes
                telemetry.gauge_set(
                    "mem.serving.kv_pool_bytes.latent",
                    self.context.latent_bytes + self.ring.latent_bytes)
        self.state_layers = [i for i, lc in enumerate(layout)
                             if lc.ssm_state or lc.conv_tail]
        # of them, those that keep a conv tail and NO recurrent state
        self.tail_layers = [i for i in self.state_layers
                            if layout[i].tail_only]
        self.state_slots = int(slots) + 1 if self.state_layers else 0
        self.state_slot_bytes = 0
        if self.state_layers:
            import numpy as np

            if slots < 1:
                raise ValueError("a model with per-slot state needs the "
                                 "engine's slot count")
            for i in self.state_layers:
                lc = layout[i]
                if lc.ssm_state:
                    self.state_slot_bytes += int(np.prod(lc.ssm_state)) \
                        * np.dtype(lc.state_dtype).itemsize
                self.state_slot_bytes += \
                    int(np.prod(lc.conv_tail)) * np.dtype(dtype).itemsize
            telemetry.gauge_set("mem.serving.state_pool_bytes",
                                self.state_pool_bytes)
            self.note_state_slots(0)
            costmodel.refresh_ledger()

    @property
    def has_state(self) -> bool:
        return bool(self.state_layers)

    @property
    def state_pool_bytes(self) -> int:
        return self.state_slots * self.state_slot_bytes

    def note_state_slots(self, seated: int):
        """The ledger's share of the state class that seated requests
        hold."""
        if self.state_layers:
            self._state_seated = int(seated)
            telemetry.gauge_set("mem.serving.state_pool_bytes.used",
                                self._state_seated * self.state_slot_bytes)

    def state_names(self) -> List[str]:
        """The program feed names of the state class's arrays."""
        return [n for i in self.state_layers
                for n in state_array_names(i, self.layout[i].tail_only)]

    def _state_arrays(self) -> Dict[str, Any]:
        import jax.numpy as jnp

        out = {}
        for i in self.state_layers:
            lc = self.layout[i]
            *state, tail = state_array_names(i, lc.tail_only)
            for name in state:
                out[name] = jnp.zeros(
                    (self.state_slots,) + tuple(lc.ssm_state),
                    lc.state_dtype)
            out[tail] = jnp.zeros((self.state_slots,) + tuple(lc.conv_tail),
                                  self.context.dtype)
        return out

    @property
    def pool_bytes(self) -> int:
        return self.context.pool_bytes + (self.ring.pool_bytes
                                          if self.ring else 0)

    def make_arrays(self) -> Dict[str, Any]:
        out = self.context.make_arrays()
        if self.ring is not None:
            out.update(self.ring.make_arrays())
        out.update(self._state_arrays())
        return out

    def pages_for_tokens(self, tokens: int) -> Tuple[int, int]:
        """(context pages, ring pages) a request of `tokens` needs: the
        ring never more than a slot's ring."""
        need = self.context.pages_for_tokens(tokens)
        return need, min(need, self.ring_slot_pages)

    def check_fits(self, tokens: int):
        """Typed refusal of a request that could never be seated, by
        whichever class it is that cannot hold it."""
        self.context.check_fits(tokens)
        ring_need = self.pages_for_tokens(tokens)[1]
        if self.ring is not None and ring_need > self.ring.capacity_pages:
            telemetry.counter_add("decode.kv_refusals", 1, klass=self.RING)
            raise KVCacheExhaustedError(
                f"request needs a ring of {ring_need} pages but the ring "
                f"pool holds {self.ring.capacity_pages}")

    def try_alloc(self, context_need: int, ring_need: int
                  ) -> Optional[Tuple[List[int], List[int]]]:
        """Pages of both classes, or None when either class cannot seat
        the request now (nothing is then taken from the other)."""
        pages = self.context.try_alloc(context_need)
        if not pages:
            return None
        if self.ring is None or ring_need == 0:
            return pages, []
        try:
            ring = self.ring.try_alloc(ring_need)
        except BaseException:     # an injected decode.kv_alloc fault
            self.context.free(pages)
            raise
        if not ring:
            self.context.free(pages)
            return None
        return pages, ring

    def free(self, pages: List[int], ring: List[int]):
        self.context.free(pages)
        if ring:
            self.ring.free(ring)

    def audit(self, owned: List[int] = None,
              owned_ring: List[int] = None) -> List[str]:
        problems = self.context.audit(owned)
        if self.ring is not None:
            problems += [f"ring: {p}" for p in self.ring.audit(owned_ring)]
        return problems

    def stats(self) -> Dict[str, Any]:
        out = self.context.stats()
        if self.ring is not None:
            out["ring"] = dict(self.ring.stats(),
                               pages_per_slot=self.ring_slot_pages,
                               window=self.window)
            out["pool_bytes_total"] = self.pool_bytes
        if self.state_layers:
            out["state"] = {"layers": len(self.state_layers),
                            "slots": self.state_slots - 1,
                            "slot_bytes": self.state_slot_bytes,
                            "pool_bytes": self.state_pool_bytes,
                            "used_bytes": self._state_seated
                            * self.state_slot_bytes}
        return out
