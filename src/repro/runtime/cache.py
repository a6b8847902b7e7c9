"""ProgramCache — the process-wide compile cache of the runtime layer.

One cache replaces the three ad-hoc ones that grew over PRs 1-3
(``functional.compile_*`` rebuilt per call site, each ``Infer``'s
``self._step`` keyed on ``id(optimizer)``, ``PredictiveEngine._programs``
keyed per engine). Cache-key anatomy (DESIGN.md §8):

    (spec.key,            # semantic identity of the program
     in/out kinds+donate, # argument roles + donation plan (replace()
                          # variants of one spec must not collide)
     spec.precision,      # mixed-precision policy token — the compute
                          # cast is traced INSIDE the body, so the fp32
                          # masters' abstract dtypes cannot carry it
     placement,           # Placement is frozen/hashable: mesh + axes + mode
     state_token,         # store generation: particle-set changes invalidate
     abstract(args))      # (treedef, shape, dtype) per argument — request
                          # batches are power-of-two padded *before* lookup
                          # (bucketing.py), so mixed sizes share programs

Notably NOT in the key: the engine/Infer/PD instance. Train, predict,
and serve over the same module+store therefore share programs — a serve
engine opened after a second engine on the same store compiles nothing.

``stats`` distinguishes hits (key present), misses (key absent), and
cold_compiles (a program was actually built — misses served from an AOT
``preload`` are not cold). A cold compile only builds the jit wrapper;
the trace and XLA compile run at the program's first call.
``snapshot_stats()`` adds the process's ``backend_compiles`` and
``compile_s`` (``runtime/compiles.py``), which count those, for every
jit in the process. ``aot_dump``/``preload`` are the ahead-of-time
serialization hook: programs export via ``jax.export`` to one file per
cache key so a warm process can be seeded without recompiling.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core.store import Placement
from ..obs import trace as _trace
from . import compiles
from .program import Program, ProgramSpec, abstract_key, lower


def _key_fingerprint(key: Tuple) -> str:
    return hashlib.sha256(repr(key).encode()).hexdigest()[:16]


class ProgramCache:
    """Process-wide (or private, for tests) plan -> Program cache.

    Bounded LRU: ``max_programs`` caps resident compiled programs so a
    long-lived process that churns stores/placements/closures cannot
    grow without bound; evicted programs that are still referenced keep
    working (the cache only forgets them), and a re-lookup recompiles.
    """

    def __init__(self, max_programs: int = 512):
        self._lock = threading.Lock()
        self._programs: "OrderedDict[Tuple, Program]" = OrderedDict()
        self._preloaded: Dict[Tuple, Program] = {}
        self.max_programs = max_programs
        self.stats = {"hits": 0, "misses": 0, "cold_compiles": 0,
                      "evictions": 0}
        compiles.install()

    # -- key construction ----------------------------------------------------
    @staticmethod
    def cache_key(spec: ProgramSpec, placement: Optional[Placement], args,
                  state_token=None, arg_keys: Optional[Sequence] = None
                  ) -> Tuple:
        # in/out kinds and the donation plan are part of program identity:
        # two specs sharing a semantic key but differing in donation (a
        # dataclasses.replace variant) must not collide
        abstract = tuple(
            arg_keys[i] if arg_keys is not None and arg_keys[i] is not None
            else abstract_key(a)
            for i, a in enumerate(args))
        # spec.precision is part of program identity: a bf16-compute
        # program consumes the SAME fp32 master inputs as its fp32 twin
        # (the cast is traced inside the body), so abstract dtypes alone
        # cannot tell them apart. Changing precision = cold compile;
        # re-running the same precision = warm hit (test_precision.py).
        return (spec.key, spec.in_kinds, spec.out_kinds, spec.donate,
                spec.precision, placement or Placement(), state_token,
                abstract)

    # -- the lookup path -----------------------------------------------------
    def lookup(self, spec: ProgramSpec, placement: Optional[Placement],
               args, state_token=None, arg_keys: Optional[Sequence] = None
               ) -> Tuple[Program, bool]:
        """(program, hit). On miss the spec is lowered + jitted (a *cold
        compile*) unless an AOT-preloaded program covers the key.

        ``arg_keys`` lets hot paths pass precomputed ``abstract_key``
        entries (None entries are computed here) — serving engines cache
        the stacked-params key between store commits so a request never
        re-flattens the whole parameter tree."""
        key = self.cache_key(spec, placement, args, state_token, arg_keys)
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                self.stats["hits"] += 1
                _trace.instant("cache.hit", "runtime", program=spec.name)
                return prog, True
            self.stats["misses"] += 1
            _trace.instant("cache.miss", "runtime", program=spec.name)
            pre = self._preloaded.pop(key, None)
            if pre is not None:
                self._insert(key, pre)
                return pre, False
        # the cold compile happens OUTSIDE the lock; the span brackets
        # trace + jit dispatch-cache population for this key
        with _trace.span("runtime.lower", "runtime", program=spec.name):
            built = lower(spec, placement, args, cache_key=key)
        with self._lock:
            prog = self._programs.get(key)
            if prog is None:
                prog = built
                self._insert(key, built)
                self.stats["cold_compiles"] += 1
        return prog, False

    def _insert(self, key, prog):
        """Insert + LRU-evict (lock held)."""
        self._programs[key] = prog
        self._programs.move_to_end(key)
        while len(self._programs) > self.max_programs:
            self._programs.popitem(last=False)
            self.stats["evictions"] += 1

    def program(self, spec: ProgramSpec, placement: Optional[Placement],
                args, state_token=None,
                arg_keys: Optional[Sequence] = None) -> Program:
        return self.lookup(spec, placement, args, state_token, arg_keys)[0]

    def run(self, spec: ProgramSpec, *args,
            placement: Optional[Placement] = None, state_token=None):
        """plan -> (cached) lower -> execute in one call."""
        return self.program(spec, placement, args, state_token)(*args)

    # -- introspection -------------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)

    def snapshot_stats(self) -> Dict[str, Any]:
        with self._lock:
            s = dict(self.stats)
            s["programs"] = len(self._programs)
            total = s["hits"] + s["misses"]
            s["hit_rate"] = s["hits"] / total if total else 0.0
        s.update(compiles.snapshot())
        return s

    def programs(self) -> List[Program]:
        """The resident programs, least recently used first."""
        with self._lock:
            return list(self._programs.values())

    def program_costs(self, compute: bool = False) -> List[Dict[str, Any]]:
        """Per-entry cost attribution (obs.device): name, key
        fingerprint, particle count, eager per-device param bytes, and —
        when already analyzed or ``compute=True`` — the FLOPs/bytes
        ``Program.cost()`` dict (compute forces one analysis compile per
        not-yet-analyzed entry; AOT-preloaded entries stay None)."""
        with self._lock:
            items = list(self._programs.items())
        return [{
            "name": prog.name,
            "fingerprint": _key_fingerprint(key),
            "num_particles": prog.num_particles,
            "param_bytes_per_device": prog.param_bytes_per_device,
            "cost": prog.cost() if compute else prog.cost_if_computed(),
        } for key, prog in items]

    def clear(self):
        with self._lock:
            self._programs.clear()
            self._preloaded.clear()

    # -- AOT serialization hook ---------------------------------------------
    def aot_dump(self, directory: str) -> Dict[str, str]:
        """Serialize every cached program via ``jax.export`` to
        ``<fingerprint>.jaxprog`` (+ a manifest.json mapping fingerprints
        to spec names). Programs that cannot be exported (e.g. exotic
        custom calls) are skipped. Returns {fingerprint: name}."""
        from jax import export as jax_export
        os.makedirs(directory, exist_ok=True)
        with self._lock:
            items = list(self._programs.items())
        manifest = {}
        for key, prog in items:
            try:
                exported = jax_export.export(prog.fn)(*prog.abstract_args)
                blob = exported.serialize()
            except Exception:   # best-effort: AOT is an optimization only
                continue
            fp = _key_fingerprint(key)
            with open(os.path.join(directory, f"{fp}.jaxprog"), "wb") as f:
                f.write(blob)
            manifest[fp] = prog.name
        with open(os.path.join(directory, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        return manifest

    def preload(self, spec: ProgramSpec, placement: Optional[Placement],
                args, blob: bytes, state_token=None):
        """Seed the cache for one key from ``aot_dump`` output: the next
        miss on that key deserializes instead of cold-compiling."""
        from jax import export as jax_export
        exported = jax_export.deserialize(blob)
        key = self.cache_key(spec, placement, args, state_token)
        prog = Program(exported.call, spec.name, key, 0, None, spec.donate)
        with self._lock:
            self._preloaded[key] = prog


# ---------------------------------------------------------------------------
# the process-wide cache
# ---------------------------------------------------------------------------

_GLOBAL = ProgramCache()


def global_cache() -> ProgramCache:
    return _GLOBAL


def jit_program(name: str, key: Tuple, fn, args, donate: Tuple[int, ...] = ()
                ) -> Program:
    """Cached plain-jit through the shared cache (no mesh semantics): the
    compile path for host-driven single-network programs — the NEL
    backend's per-particle step/forward and the paper's sequential
    baselines. ``fn`` may be a fresh closure per call; only ``key`` and
    the argument shapes decide identity."""
    spec = ProgramSpec(name=name, key=key, make=lambda ctx: fn,
                       in_kinds=("replicated",) * len(args), out_kinds=None,
                       donate=donate)
    return _GLOBAL.program(spec, None, args)
