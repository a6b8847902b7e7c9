# repro.runtime — the single plan/compile/execute layer (DESIGN.md §8).
#
# program.py   — ProgramSpec / BuildCtx / Program + lowering (the ONLY
#                jax.jit site for fused particle programs)
# cache.py     — process-wide ProgramCache (hit/miss/cold-compile stats,
#                AOT serialization hook) + jit_program for host-driven
#                single-network programs (NEL steps, baselines)
# compiles.py  — the process's one jax.monitoring compile listener
#                (backend_compiles, compile_s, runtime.compile spans)
# specs.py     — generic spec builders (ensemble step/predict, map_step)
# backends.py  — Runtime protocol: NelRuntime / CompiledRuntime
# bucketing.py — power-of-two batch bucketing shared with serve/
from .backends import (BACKENDS, CompiledRuntime, NelRuntime, Runtime,
                       make_runtime)
from .bucketing import bucket_size, pad_rows
from .cache import ProgramCache, global_cache, jit_program
from .program import (BuildCtx, Program, ProgramSpec, abstract_key, ident,
                      lower)
from . import specs
