"""ParticleStore: mesh-sharded particle state as the single source of truth.

Both runtime backends read and write the same state through this store
(Tran et al. 2018's design point: one program representation, placement
decided by shardings):

  * canonical form — one *stacked* pytree per state key ("params",
    "opt_state", "swag", ...) with a leading particle axis, placed on a
    device mesh via ``NamedSharding`` derived from
    ``sharding/rules.tree_shardings(..., particle_axis=...)``;
  * derived form — lazy per-particle *views* (unstack-on-read: a view is
    just ``leaf[slot]``, staying on device until consumed) with
    dirty-tracked write-back, which is what the NEL backend's
    ``Particle.state`` maps onto.

Elastic lifecycle (DESIGN.md §9): the store allocates by **capacity, not
count**. Stacked trees are padded to a power-of-two ``capacity``; each
live particle owns one *slot* (``slot_of``), freed slots go on a
free-slot list, and a device-resident ``active_mask()`` (shape
``(capacity,)``, 1.0 at live slots) tells fused programs which rows are
real. Creating, cloning, or killing a particle **within capacity** is a
slot write / mask flip that never changes the stacked shapes — so
``generation()`` (the ProgramCache invalidation token) bumps ONLY on
capacity growth or key-schema changes (a state key seen for the first
time), never on churn. Serving and training keep their compiled
programs across arbitrary clone/kill traffic.

Consistency protocol (all transitions under one lock):

  write_view(pid)  -> row cached + marked dirty; the stale stacked row is
                      shadowed (view reads hit the row cache first)
  stacked()        -> flush: dirty rows written into the stacked tree
                      (slot-wise ``.at[s].set``), or a full restack padded
                      to capacity (free slots filled with zeros) when no
                      canonical stacked exists
  checkout()       -> flush + *move* ownership to the caller: the fused
                      epoch loop donates these buffers to XLA every step
                      (``donate_argnums``), so the store must not retain a
                      reference to memory that is about to be invalidated
  commit(stacked)  -> the fused result becomes canonical; view caches are
                      invalidated and re-derived lazily on next read

``stats`` counts every materialization (stacks, unstacks, row flushes,
commits, device placements) plus the lifecycle counters (mask
invalidations, capacity growths) so tests can assert that churn within
capacity touches neither the compiler nor the host.
"""
from __future__ import annotations

import heapq
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs import trace as _trace
from ..sharding import rules


# ---------------------------------------------------------------------------
# placement plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class Placement:
    """The 2D placement plan: a ``(particle x model)`` mesh plus which
    mesh axis carries which role. ``particle_axis`` shards the leading
    stacked-particle dimension; ``model_axis`` shards *within* one
    particle (tensor-parallel trailing dims via ``sharding/rules`` plus
    the activation constraints of ``sharding/policy``), so a single
    particle that does not fit on one chip spreads over the model axis.
    ``mesh=None`` (the default) keeps state wherever jax puts it — the
    single-device fast path with no resharding cost.

    Equality / hashing are by *plan*, not object identity: two
    placements over separately-built but identical meshes (same axis
    names, sizes, and device order) compare equal, so re-placement onto
    the same 2D plan is a 100% warm hit in the ProgramCache, while any
    mesh-shape or mode change invalidates (the cache key embeds this
    object directly)."""
    mesh: Any = None
    particle_axis: Optional[str] = "data"
    mode: str = "tp"  # within-particle sharding rules mode (sharding/rules)
    model_axis: Optional[str] = "model"

    # -- plan identity -------------------------------------------------------
    def plan_key(self) -> tuple:
        """Hashable value identity of the plan (what cache keys see)."""
        if self.mesh is None:
            mesh_key = None
        else:
            mesh_key = (tuple(self.mesh.axis_names),
                        tuple(int(self.mesh.shape[a])
                              for a in self.mesh.axis_names),
                        tuple(int(d.id) for d in
                              np.asarray(self.mesh.devices).flat))
        return (mesh_key, self.particle_axis, self.model_axis, self.mode)

    def __eq__(self, other) -> bool:
        if not isinstance(other, Placement):
            return NotImplemented
        return self.plan_key() == other.plan_key()

    def __hash__(self) -> int:
        return hash(self.plan_key())

    @staticmethod
    def auto(particle_axis: str = "data", mode: str = "tp",
             model: Any = 1, *, params_bytes: Optional[int] = None,
             param_tree: Any = None, precision: Any = None,
             device_memory_bytes: Optional[int] = None) -> "Placement":
        """Mesh over all local devices. ``model`` sets the model-axis
        size (particles get the remaining ``n_devices // model`` ways);
        ``model="auto"`` picks the smallest model-axis size whose
        per-device parameter shard fits the device memory budget, from
        ``params_bytes`` (per-particle parameter bytes) vs the local
        device's reported memory (``launch.mesh.pick_model_axis``) —
        multi-host launches call this after ``launch.distributed
        .initialize()`` so ``jax.devices()`` spans every process.

        The estimate is *precision-aware*: instead of raw
        ``params_bytes`` you can hand over a ``param_tree`` (real arrays
        or ``jax.eval_shape`` structs) plus the ensemble's ``precision``
        policy; the bytes are then counted at the policy's MASTER
        itemsize (``core.precision.tree_bytes``), so a bf16 store stops
        oversizing the model axis by 2x. An explicit ``params_bytes``
        with a ``precision`` is rescaled from the fp32 baseline the
        callers historically passed."""
        from ..launch.mesh import make_bench_mesh, pick_model_axis
        n = len(jax.devices())
        if model == "auto":
            from .precision import get as _get_prec, tree_bytes
            if params_bytes is None and param_tree is not None:
                params_bytes = tree_bytes(param_tree, precision)
            elif params_bytes is not None and precision is not None:
                prec = _get_prec(precision)
                params_bytes = int(params_bytes
                                   * prec.master.itemsize / 4)
            model = pick_model_axis(params_bytes or 0, n,
                                    device_memory_bytes=device_memory_bytes)
        model = int(model)
        if n <= 1 and model <= 1:
            return Placement(mesh=None)
        return Placement(mesh=make_bench_mesh(n, model=model),
                         particle_axis=particle_axis, mode=mode)

    # -- axis sizes ----------------------------------------------------------
    def _axis_size(self, axis: Optional[str]) -> int:
        if self.mesh is None or axis is None:
            return 1
        return int(dict(self.mesh.shape).get(axis, 1))

    def particle_axis_size(self) -> int:
        return self._axis_size(self.particle_axis)

    def model_axis_size(self) -> int:
        return self._axis_size(self.model_axis)

    # -- sharding derivation -------------------------------------------------
    def shardings(self, stacked_tree):
        """NamedSharding tree for a stacked state pytree (leading particle
        axis -> particle_axis, trailing dims -> sharding/rules over the
        model axis)."""
        if self.mesh is None:
            return None
        return rules.tree_shardings(self.mesh, stacked_tree, self.mode,
                                    self.particle_axis,
                                    model_axis=self.model_axis)

    def activation_policy(self) -> Optional[Dict[str, Any]]:
        """The ``sharding/policy`` name -> PartitionSpec map fused
        programs trace under (None when the plan has no model axis to
        shard over — never constrain intermediates on particle-only
        placements, where forcing replication would be pure overhead)."""
        if self.mesh is None or self.model_axis_size() <= 1:
            return None
        from ..sharding.policy import tp_activation_policy
        return tp_activation_policy(dict(self.mesh.shape), self.model_axis)

    def replicated(self, tree):
        """Fully-replicated shardings (batches: every particle sees the
        same data under deep-ensemble semantics)."""
        if self.mesh is None:
            return None
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda _: sh, tree)

    def _axis_fits(self, n: int, axis: Optional[str]) -> Optional[str]:
        if self.mesh is None or axis is None:
            return None
        size = dict(self.mesh.shape).get(axis)
        return axis if size and n % size == 0 else None

    def vector(self, n: int):
        """Sharding for per-particle scalars stacked to (n,) (losses)."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh,
                             P(self._axis_fits(n, self.particle_axis)))

    def matrix(self, n: int, d: int):
        """Sharding for the flattened (n, D) particle-parameter matrix
        (SVGD): particles over the particle axis, D over the model axis."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh,
                             P(self._axis_fits(n, self.particle_axis),
                               self._axis_fits(d, self.model_axis)))

    def gathered_matrix(self, d: int):
        """Sharding of the (n, D) matrix *after* the all-gather over the
        particle axis ONLY: every device holds all particles' rows (the
        SVGD kernel matrix needs all-to-all), D still sharded over the
        model axis — the collective never widens past the particle axis."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh,
                             P(None, self._axis_fits(d, self.model_axis)))

    def spmd_axis(self, n: int) -> Optional[str]:
        """vmap spmd_axis_name when the particle count divides the mesh
        axis — this is what lets GSPMD distribute particles."""
        return self._axis_fits(n, self.particle_axis)


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

def _stack_rows(rows):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


def _leading_dim(tree) -> int:
    return jax.tree.leaves(tree)[0].shape[0]


def _leading_or_none(tree) -> Optional[int]:
    leaves = jax.tree.leaves(tree)
    return leaves[0].shape[0] if leaves else None


def _pow2_at_least(n: int) -> int:
    cap = 1
    while cap < n:
        cap *= 2
    return cap


@jax.jit
def _ROW_WRITE(st, row, slot):
    return jax.tree.map(
        lambda a, r: jax.lax.dynamic_update_slice_in_dim(
            a, r.astype(a.dtype)[None], slot, 0), st, row)


@jax.jit
def _COPY_SLOT(st, src, dst):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_update_slice_in_dim(
            a, jax.lax.dynamic_slice_in_dim(a, src, 1, 0), dst, 0), st)


@jax.jit
def _COPY_SLOT_JITTER(st, src, dst, eps, key):
    leaves, tdef = jax.tree.flatten(st)
    keys = jax.random.split(key, len(leaves))
    out = []
    for a, k in zip(leaves, keys):
        row = jax.lax.dynamic_slice_in_dim(a, src, 1, 0)
        if jnp.issubdtype(a.dtype, jnp.floating):
            row = row + eps.astype(a.dtype) * jax.random.normal(
                k, row.shape, a.dtype)
        out.append(jax.lax.dynamic_update_slice_in_dim(a, row, dst, 0))
    return tdef.unflatten(out)


class ParticleStore:
    """Canonical holder of all per-particle state of one PushDistribution.

    ``capacity`` preallocates slots (rounded up to a power of two) so the
    first ``capacity`` registrations never bump ``generation()``; the
    default 0 grows on demand (1, 2, 4, 8, ... — one generation bump per
    doubling)."""

    def __init__(self, placement: Optional[Placement] = None,
                 capacity: int = 0, precision=None):
        self.placement = placement or Placement()
        # the ensemble's Precision policy (core.precision). The store
        # itself is dtype-agnostic — leaves keep whatever dtype is
        # committed — but it carries the policy so checkpointing can
        # persist it and obs/serve can report/derive from it.
        from .precision import get as _resolve_precision
        self.precision = _resolve_precision(precision)
        self.capacity = _pow2_at_least(capacity) if capacity > 0 else 0
        self._slot_of: Dict[int, int] = {}          # pid -> slot
        self._free: List[int] = list(range(self.capacity))  # min-heap
        self._activated: Set[int] = set()           # slots with data landed
        # in-flight full checkouts: key -> (capacity, slots) at checkout
        self._checkout_cohort: Dict[str, Any] = {}
        self._stacked: Dict[str, Any] = {}          # key -> padded pytree
        self._rows: Dict[str, Dict[int, Any]] = {}  # key -> {slot: row tree}
        self._dirty: Dict[str, Set[int]] = {}       # key -> slots newer
        self._present: Dict[str, Set[int]] = {}     # key -> slots holding it
        self._lock = threading.RLock()
        # (generation, per-key edit count): serving engines cache the
        # flushed stacked tree against this and only re-read after a
        # write/commit/registration (engine.py's param_refreshes stat)
        self._gen = 0
        self._versions: Dict[str, int] = {}
        self._mask_cache: Any = None
        self.stats = {"stacks": 0, "unstacks": 0, "row_flushes": 0,
                      "commits": 0, "device_puts": 0, "checkouts": 0,
                      "mask_invalidations": 0, "capacity_growths": 0,
                      "slot_clones": 0}

    # -- registry / slot allocation ------------------------------------------
    @property
    def pids(self) -> List[int]:
        """Live pids in slot order (row s of a padded stacked tree belongs
        to ``pids``'s entry with slot s)."""
        with self._lock:
            return [pid for pid, _ in
                    sorted(self._slot_of.items(), key=lambda kv: kv[1])]

    def register(self, pid: int) -> int:
        """Allocate a slot for ``pid``. Reuses a freed slot when one
        exists (no shape change, no generation bump); grows capacity to
        the next power of two — and bumps the generation — only when
        full.

        The slot is allocated but NOT yet live in ``active_mask()``:
        activation happens on the first state write/clone into the slot,
        so a concurrent serve between register and the data landing
        still masks the slot off (it would otherwise read the previous
        occupant's stale row, or zeros)."""
        with self._lock:
            if pid in self._slot_of:
                raise ValueError(f"pid {pid} already registered")
            if not self._free:
                self._grow(_pow2_at_least(self.capacity + 1))
            slot = heapq.heappop(self._free)
            self._slot_of[pid] = slot
            # a reused slot's data in any in-flight full checkout now
            # belongs to the PREVIOUS occupant: the new owner's writes
            # must survive that checkout's commit
            for _, cohort_slots in self._checkout_cohort.values():
                cohort_slots.discard(slot)
            return slot

    def unregister(self, pid: int) -> int:
        """Free ``pid``'s slot: its rows are dropped, the slot goes on the
        free list, and the active mask flips to 0 there. The stale row
        inside any stacked tree stays (masked out) until a clone reuses
        the slot — so unregister never restacks, re-places, or changes
        ``generation()``."""
        with self._lock:
            slot = self._slot_of.pop(pid)   # KeyError for unknown pid
            heapq.heappush(self._free, slot)
            self._activated.discard(slot)
            for present in self._present.values():
                present.discard(slot)
            for rows in self._rows.values():
                rows.pop(slot, None)
            for dirty in self._dirty.values():
                dirty.discard(slot)
            self._invalidate_mask()
            return slot

    def _grow(self, new_capacity: int):
        """Capacity growth (lock held): pad every stacked tree with zero
        rows to the new power-of-two capacity. This is the ONE lifecycle
        operation that changes stacked shapes, so it bumps the
        generation (compiled programs over the old capacity are stale)."""
        old = self.capacity
        self.capacity = new_capacity
        for s in range(old, new_capacity):
            heapq.heappush(self._free, s)
        pad_n = new_capacity - old
        for key, st in list(self._stacked.items()):
            if not jax.tree.leaves(st):
                continue
            st = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.zeros((pad_n,) + x.shape[1:], x.dtype)]), st)
            self._stacked[key] = self._place(st)
        self._gen += 1
        self.stats["capacity_growths"] += 1
        _trace.instant("store.generation_bump", "store",
                       capacity=new_capacity, generation=self._gen)
        self._invalidate_mask()

    def slot_of(self, pid: int) -> int:
        with self._lock:
            return self._slot_of[pid]

    def live_count(self) -> int:
        with self._lock:
            return len(self._slot_of)

    def free_slots(self) -> int:
        with self._lock:
            return len(self._free)

    # -- active mask ---------------------------------------------------------
    def _invalidate_mask(self):
        self._mask_cache = None
        self.stats["mask_invalidations"] += 1

    def active_mask(self):
        """Device-resident ``(capacity,)`` float32 mask — 1.0 at
        *activated* slots (registered AND first data landed). Cached
        between lifecycle events (engines re-read it per request for
        free); replicated on the mesh so fused programs can reduce over
        live rows without a host round trip."""
        with self._lock:
            if self._mask_cache is None:
                m = np.zeros((self.capacity,), np.float32)
                for slot in self._activated:
                    m[slot] = 1.0
                arr = jnp.asarray(m)
                if self.placement.mesh is not None:
                    arr = jax.device_put(
                        arr, NamedSharding(self.placement.mesh, P()))
                self._mask_cache = arr
            return self._mask_cache

    def snapshot(self, key: str):
        """(version, active mask, canonical padded stacked tree) read
        atomically under the store lock — THE way for a concurrent
        consumer (serving, fused predict) to pair a mask with params:
        separate reads can interleave with churn and serve a slot whose
        data has not landed yet."""
        with self._lock:
            return ((self._gen, self._versions.get(key, 0)),
                    self.active_mask(), self._flush(key))

    def live_slots(self) -> List[int]:
        """Sorted activated slots (host-side; no device sync)."""
        with self._lock:
            return sorted(self._activated)

    def version(self, key: str):
        """Monotone token that changes whenever `key`'s canonical content
        could have (write/commit/discard/registration). Serving engines
        compare tokens instead of re-flushing per request."""
        with self._lock:
            return (self._gen, self._versions.get(key, 0))

    def generation(self) -> int:
        """The compile-invalidation component of ``version``: bumps ONLY
        on capacity growth or key-schema changes (a state key created for
        the first time). Within-capacity register/clone/kill are slot
        writes that leave it — and therefore every cached Program —
        untouched. ProgramCache keys carry this (not the per-key edit
        count: content edits must reuse compiled programs)."""
        with self._lock:
            return self._gen

    def _bump(self, key: str):
        self._versions[key] = self._versions.get(key, 0) + 1

    def keys(self) -> List[str]:
        """Every state key any particle holds (stacked or row form)."""
        with self._lock:
            return sorted(set(self._present) | set(self._stacked))

    def __len__(self) -> int:
        return len(self._slot_of)

    def _subset(self, pids: Optional[Sequence[int]]) -> Optional[List[int]]:
        """None -> the canonical capacity-padded path. An explicit pid
        list keeps the dense "index i <-> pids[i]" contract — it only
        collapses onto the canonical path when that is the same thing
        (full live set, slot order, no free slots)."""
        if pids is None:
            return None
        pids = list(pids)
        if pids == self.pids and len(pids) == self.capacity:
            return None
        missing = [p for p in pids if p not in self._slot_of]
        if missing:
            raise KeyError(f"unregistered pids {missing}")
        return pids

    def _mark_present(self, key: str, slot: int):
        present = self._present.get(key)
        if present is None:
            # key-schema change: a state key the store has never seen —
            # fused programs traced without it are stale in principle
            present = self._present[key] = set()
            if key not in self._stacked:
                self._gen += 1
        present.add(slot)
        if slot not in self._activated:
            # first data landing activates the slot in the mask
            self._activated.add(slot)
            self._invalidate_mask()

    def _demote_to_rows(self, key: str):
        """Replace the stacked form with per-particle rows (lock held).
        Needed before subset checkout/commit: a stale stacked tree must
        not shadow rows that are about to diverge from it."""
        st = self._stacked.get(key)
        if st is None:
            return
        rows = self._rows.setdefault(key, {})
        for slot in self._present.get(key, set()):
            if slot not in rows:
                rows[slot] = jax.tree.map(lambda x, s=slot: x[s], st)
                self.stats["unstacks"] += 1
        self._stacked.pop(key, None)
        self._dirty.pop(key, None)

    # -- per-particle views (unstack-on-read, dirty-tracked write-back) ------
    def has(self, key: str, pid: int) -> bool:
        with self._lock:
            slot = self._slot_of[pid]
            return slot in self._present.get(key, ())

    def _read_slot(self, key: str, slot: int):
        """Lazy view of one slot's entry (lock held): cached row if
        present, else sliced out of the canonical stacked tree."""
        rows = self._rows.setdefault(key, {})
        if slot in rows:
            return rows[slot]
        if slot not in self._present.get(key, ()):
            raise KeyError(f"store has no {key!r} in slot {slot}")
        st = self._stacked.get(key)
        if st is None:
            raise KeyError(f"store has no {key!r} in slot {slot}")
        row = jax.tree.map(lambda x: x[slot], st)
        rows[slot] = row
        self.stats["unstacks"] += 1
        return row

    def read(self, key: str, pid: int):
        """Lazy view of one particle's entry (stays on device)."""
        with self._lock:
            try:
                return self._read_slot(key, self._slot_of[pid])
            except KeyError:
                raise KeyError(f"store has no {key!r} for particle {pid}")

    def write(self, key: str, pid: int, tree):
        """Write-back from a view: the row shadows the stacked entry until
        the next flush."""
        with self._lock:
            slot = self._slot_of[pid]
            self._mark_present(key, slot)
            self._rows.setdefault(key, {})[slot] = tree
            self._dirty.setdefault(key, set()).add(slot)
            self._bump(key)

    def discard(self, key: str, pid: int):
        with self._lock:
            if key in self._stacked:   # stacked would no longer cover this pid
                raise ValueError(
                    f"cannot delete {key!r} of particle {pid}: the key is "
                    "stacked; delete is only supported for row-only keys")
            slot = self._slot_of[pid]
            rows = self._rows.get(key, {})
            if slot not in rows:
                raise KeyError(key)
            del rows[slot]
            self._present.get(key, set()).discard(slot)
            self._dirty.get(key, set()).discard(slot)
            self._bump(key)

    def keys_for(self, pid: int) -> List[str]:
        with self._lock:
            return [k for k in set(self._present) | set(self._stacked)
                    if self.has(k, pid)]

    # -- canonical stacked form ---------------------------------------------
    def _flush(self, key: str):
        """Make the capacity-padded stacked tree canonical for `key`
        (lock held). Free / absent slots are zero rows, gated off by the
        active mask inside fused programs."""
        st = self._stacked.get(key)
        dirty = self._dirty.get(key, set())
        cap = self.capacity
        lead = None if st is None else _leading_or_none(st)
        if st is not None and (lead is None or lead == cap) and not dirty:
            return st
        # slot-wise write-back only pays off while few rows are dirty:
        # each row write copies the whole stacked tree, so beyond ~half
        # the rows a single restack moves strictly less data
        if (st is not None and lead == cap
                and len(dirty) <= max(1, cap // 2)):
            on_mesh = self.placement.mesh is not None
            for slot in sorted(dirty):
                row = self._rows[key][slot]
                if on_mesh:
                    # eager per-leaf scatter preserves the NamedSharding
                    st = jax.tree.map(lambda s, r: s.at[slot].set(r),
                                      st, row)
                else:
                    st = self._row_write(st, row, slot)
            self.stats["row_flushes"] += len(dirty)
        else:
            # no canonical stacked (or capacity grew): full padded restack
            present = sorted(self._present.get(key, ()))
            if not present:
                raise KeyError(key)
            rows = {s: self._read_slot(key, s) for s in present}
            template = next((r for r in rows.values()
                             if jax.tree.leaves(r)), None)
            if template is None:      # key holds leafless trees (None)
                st = _stack_rows([rows[s] for s in present])
            else:
                pad = jax.tree.map(jnp.zeros_like, template)
                st = _stack_rows([rows.get(s, pad) for s in range(cap)])
                # the stack now holds every row: drop the cached row copies
                # instead of keeping the key twice on device (reads slice
                # them back lazily)
                self._rows.pop(key, None)
            self.stats["stacks"] += 1
        st = self._place(st)
        self._stacked[key] = st
        self._dirty[key] = set()
        return st

    @staticmethod
    def _row_write(st, row, slot):
        """Write one row into the stacked tree as ONE fused call (the
        slot rides in as a traced scalar, so every slot shares the same
        executable): churn write-back costs one dispatch, not one eager
        scatter per leaf. Pure data movement — deliberately NOT a
        ProgramCache entry, so churn stays invisible to the compile
        stats the zero-recompile gates assert on."""
        return _ROW_WRITE(st, row, jnp.asarray(slot, jnp.int32))

    def _place(self, st):
        pl = self.placement
        if pl.mesh is None:
            return st
        want = pl.shardings(st)
        leaves = jax.tree.leaves(st)
        want_leaves = jax.tree.leaves(want)
        if all(getattr(x, "sharding", None) == s
               for x, s in zip(leaves, want_leaves)):
            return st                          # already placed (commit path)
        self.stats["device_puts"] += 1
        with _trace.span("store.h2d", "store", leaves=len(leaves)):
            return jax.device_put(st, want)

    def stacked(self, key: str, pids: Optional[Sequence[int]] = None):
        """The canonical capacity-padded stacked pytree (flushing any
        dirty views first); consumers combine it with ``active_mask()``.
        With an explicit pid subset, a fresh *dense* stack of those rows
        (index i -> pids[i]) that does not disturb the canonical form."""
        with self._lock:
            sub = self._subset(pids)
            if sub is None:
                return self._flush(key)
            st = _stack_rows([self.read(key, p) for p in sub])
            self.stats["stacks"] += 1
            return st

    def dense(self, key: str, pids: Optional[Sequence[int]] = None):
        """Live rows only, stacked dense in slot order (leading dim =
        live count, no padding, no mask needed) — for consumers that
        replicate rows host-side (SWAG serve-time sampling, checkpoint)."""
        with self._lock:
            pids = self.pids if pids is None else list(pids)
            self.stats["stacks"] += 1
            st = self._stacked.get(key)
            if st is not None and not self._dirty.get(key) \
                    and _leading_or_none(st) == self.capacity:
                # one gather out of the canonical stack: no per-row copies
                # cached in the store on the way
                idx = jnp.asarray([self._slot_of[p] for p in pids])
                return jax.tree.map(lambda x: x[idx], st)
            return _stack_rows([self.read(key, p) for p in pids])

    def checkout(self, key: str, pids: Optional[Sequence[int]] = None):
        """Like ``stacked`` but transfers buffer ownership to the caller:
        the store drops its references so the fused loop may donate them
        to XLA. The caller must ``commit`` a result (or the original) back."""
        with _trace.span("store.checkout", "store", key=key), self._lock:
            sub = self._subset(pids)
            self.stats["checkouts"] += 1
            self._bump(key)
            if sub is None:
                st = self._flush(key)
                # remember which slots this checkout owns (and at what
                # capacity): a particle registered mid-run writes rows —
                # and may grow the store — that the matching commit must
                # not clobber or trip over
                self._checkout_cohort[key] = (
                    self.capacity, set(self._present.get(key, ())))
                self._stacked.pop(key, None)
                self._rows.pop(key, None)
                self._dirty.pop(key, None)
                return st
            # subset checkout: remaining particles keep their rows
            for p in sub:          # materialize before popping anything
                self.read(key, p)
            self._demote_to_rows(key)
            rows = self._rows.setdefault(key, {})
            out = [rows.pop(self._slot_of[p]) for p in sub]
            dirty = self._dirty.get(key, set())
            for p in sub:
                dirty.discard(self._slot_of[p])
            self.stats["stacks"] += 1
            return _stack_rows(out)

    def commit(self, key: str, stacked, pids: Optional[Sequence[int]] = None):
        """A fused program's output becomes canonical; views re-derive
        lazily (this is the *only* write-back of a multi-epoch fused run).
        Full commits carry the capacity-padded shape; with a pid subset,
        row i of `stacked` becomes pids[i]'s state."""
        with _trace.span("store.commit", "store", key=key), self._lock:
            sub = self._subset(pids)
            cohort = None if sub is not None \
                else self._checkout_cohort.pop(key, None)
            if sub is not None:
                n = len(sub)
            elif cohort is not None:
                n = cohort[0]      # capacity at checkout time: the store
                #                    may have grown under the fused run
            else:
                n = self.capacity
            if _leading_dim(stacked) != n:
                raise ValueError(
                    f"stacked {key!r} has leading dim "
                    f"{_leading_dim(stacked)}, expected {n}")
            self.stats["commits"] += 1
            self._bump(key)
            if sub is None:
                if key not in self._present and key not in self._stacked:
                    self._gen += 1     # key-schema change
                if cohort is None:
                    # direct commit (no prior checkout): the tree speaks
                    # for every live slot, with data landing now
                    self._stacked[key] = stacked
                    for slot in self._slot_of.values():
                        self._mark_present(key, slot)
                    self._rows.pop(key, None)
                    self._dirty.pop(key, None)
                    return
                # checkout/commit round trip: the committed tree covers
                # the cohort checked out — pad it up if the store grew
                # mid-run; rows written since (a particle created
                # mid-run) stay as dirty shadows over it
                co_cap, co_slots = cohort
                if co_cap < self.capacity:
                    pad_n = self.capacity - co_cap
                    stacked = jax.tree.map(
                        lambda x: jnp.concatenate(
                            [x, jnp.zeros((pad_n,) + x.shape[1:],
                                          x.dtype)]), stacked)
                self._stacked[key] = stacked
                present = self._present.setdefault(key, set())
                live = set(self._slot_of.values())
                present |= co_slots & live
                rows = self._rows.get(key, {})
                dirty = self._dirty.get(key, set())
                for slot in co_slots:
                    rows.pop(slot, None)
                    dirty.discard(slot)
                return
            self._demote_to_rows(key)
            rows = self._rows.setdefault(key, {})
            for j, p in enumerate(sub):
                slot = self._slot_of[p]
                self._mark_present(key, slot)
                rows[slot] = jax.tree.map(lambda x, j=j: x[j], stacked)
            self.stats["unstacks"] += len(sub)

    # -- fused slot cloning (the p_clone fast path) --------------------------
    def clone_slot(self, key: str, src_pid: int, dst_pid: int,
                   jitter: float = 0.0, rng=None, prefer_row: bool = False):
        """Copy one state key from ``src_pid``'s slot into ``dst_pid``'s,
        entirely inside the canonical stacked tree: ONE fused
        slice+update dispatch (slots ride in as traced scalars, params
        optionally jittered in the same program) instead of a per-leaf
        unstack/restack round trip. The updated tree stays canonical —
        the next serving flush is a no-op.

        ``prefer_row=True`` takes the lazy row-copy path instead (a dirty
        row flushed on next use): right for cold keys like opt state,
        whose fused copy would pay a full stacked-tree copy that nothing
        is about to read. Leafless trees (``grads=None``) and mesh
        placements also use the row path — under a mesh the eager
        per-leaf flush preserves NamedShardings exactly."""
        with self._lock:
            src = self._slot_of[src_pid]
            dst = self._slot_of[dst_pid]
            if key in self._checkout_cohort:
                # the source data was moved out (and likely donated) by
                # a fused run — fail with the real reason, not a
                # missing-data KeyError
                raise RuntimeError(
                    f"{key!r} is checked out by an in-flight fused run; "
                    "commit it back before cloning")
            if src not in self._present.get(key, ()):
                raise KeyError(f"store has no {key!r} for particle "
                               f"{src_pid}")
            fused = self.placement.mesh is None and not prefer_row
            st = None
            if fused:
                try:
                    st = self._flush(key)
                except KeyError:
                    st = None
            if st is None or not jax.tree.leaves(st):
                # row-reference copy (still lazy: no device work here
                # beyond the jitter, which only params paths request)
                row = self._read_slot(key, src)
                if jitter and rng is not None and jax.tree.leaves(row):
                    leaves, tdef = jax.tree.flatten(row)
                    keys = jax.random.split(rng, len(leaves))
                    row = tdef.unflatten([
                        l + jitter * jax.random.normal(k, l.shape, l.dtype)
                        if jnp.issubdtype(l.dtype, jnp.floating) else l
                        for l, k in zip(leaves, keys)])
                self._mark_present(key, dst)
                self._rows.setdefault(key, {})[dst] = row
                self._dirty.setdefault(key, set()).add(dst)
            else:
                if jitter and rng is not None:
                    st = _COPY_SLOT_JITTER(st, src, dst,
                                           jnp.float32(jitter), rng)
                else:
                    st = _COPY_SLOT(st, src, dst)
                self._stacked[key] = st
                self._mark_present(key, dst)
                rows = self._rows.get(key)
                if rows:
                    rows.pop(dst, None)
                self._dirty.get(key, set()).discard(dst)
                self.stats["slot_clones"] += 1
            self._bump(key)

    # -- lifecycle introspection / re-placement ------------------------------
    def rebalance(self):
        """Re-place every key's canonical stacked form against the current
        placement plan (flush, then an explicit ``_place`` even for clean
        trees — ``_flush`` alone skips placement on its early return) and
        rebuild the mask — the store half of ``pd.p_rebalance()``."""
        with self._lock:
            for key in self.keys():
                try:
                    st = self._flush(key)
                except (KeyError, ValueError):
                    continue
                if jax.tree.leaves(st):
                    self._stacked[key] = self._place(st)
            self._invalidate_mask()

    def per_device_bytes(self, key: str = "params") -> int:
        """Bytes of ``key``'s canonical state resident on ONE device
        under the current placement — the headline number 2D placement
        moves (a model axis of size m divides a replicated particle's
        footprint by ~m, modulo non-divisible leaves). Reads whatever
        form exists without flushing, placing, or bumping stats
        counters; 0 when the store holds nothing for the key."""
        def leaf_bytes(x):
            sh = getattr(x, "sharding", None)
            if sh is not None and hasattr(sh, "shard_shape"):
                try:
                    shard = sh.shard_shape(x.shape)
                except Exception:
                    return int(x.nbytes)
                return int(np.prod(shard, dtype=np.int64)
                           * np.dtype(x.dtype).itemsize)
            return int(getattr(x, "nbytes", 0))
        with self._lock:
            tree = self._stacked.get(key)
            if tree is None:
                rows = self._rows.get(key, {})
                return int(sum(leaf_bytes(l) for row in rows.values()
                               for l in jax.tree.leaves(row)))
            return int(sum(leaf_bytes(l) for l in jax.tree.leaves(tree)))

    def per_particle_bytes(self, key: str = "params") -> int:
        """Whole-ensemble bytes of ``key`` (all devices, actual leaf
        dtypes) divided by capacity — the per-particle HBM footprint the
        precision ladder moves (bench_precision's headline). 0 when the
        store holds nothing for the key."""
        with self._lock:
            tree = self._stacked.get(key)
            if tree is None:
                rows = self._rows.get(key, {})
                if not rows:
                    return 0
                row = next(iter(rows.values()))
                return int(sum(int(getattr(l, "nbytes", 0))
                               for l in jax.tree.leaves(row)))
            total = sum(int(getattr(l, "nbytes", 0))
                        for l in jax.tree.leaves(tree))
            return int(total // max(self.capacity, 1))

    def key_dtypes(self, key: str = "params") -> Dict[str, int]:
        """{dtype name: leaf count} of ``key``'s resident state — the
        dtype surface obs gauges and checkpoints record (a bf16 store
        reports {'bfloat16': ...}, masters-only fp32 {'float32': ...})."""
        out: Dict[str, int] = {}
        with self._lock:
            tree = self._stacked.get(key)
            if tree is None:
                rows = self._rows.get(key, {})
                tree = next(iter(rows.values())) if rows else None
            for leaf in jax.tree.leaves(tree) if tree is not None else ():
                name = np.dtype(leaf.dtype).name if hasattr(leaf, "dtype") \
                    else type(leaf).__name__
                out[name] = out.get(name, 0) + 1
        return out

    def lifecycle_stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "capacity": self.capacity,
                "live": len(self._slot_of),
                "free_slots": len(self._free),
                "generation": self._gen,
                "mask_invalidations": self.stats["mask_invalidations"],
                "capacity_growths": self.stats["capacity_growths"],
            }

    def snapshot_stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.stats)


# ---------------------------------------------------------------------------
# per-particle mapping facade (what Particle.state is)
# ---------------------------------------------------------------------------

class StoreState:
    """Mutable-mapping view of one particle's slice of a ParticleStore.

    ``particle.state["params"]`` reads/writes route through the store's
    view protocol, so the NEL backend and the fused backend observe one
    source of truth — there is no duplicated per-particle state dict."""

    def __init__(self, store: ParticleStore, pid: int):
        self.store = store
        self.pid = pid

    def __getitem__(self, key: str):
        return self.store.read(key, self.pid)

    def __setitem__(self, key: str, value):
        self.store.write(key, self.pid, value)

    def __delitem__(self, key: str):
        self.store.discard(key, self.pid)

    def __contains__(self, key: str) -> bool:
        return self.store.has(key, self.pid)

    def get(self, key: str, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def keys(self):
        return self.store.keys_for(self.pid)

    def __iter__(self):
        return iter(self.keys())

    def __len__(self) -> int:
        return len(self.keys())

    def __repr__(self) -> str:
        return f"StoreState(pid={self.pid}, keys={sorted(self.keys())})"
