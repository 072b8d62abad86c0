"""Platform decisions: what "on the chip" means, how kernels lower, and where
compiled programs are cached.

``on_tpu()`` is the one definition of "on the chip" every size, peak, label
and lowering decision reads. Pallas kernels run in interpret mode on the CPU
(tests, rehearsals) and as compiled Mosaic on a TPU; AOT compilation against
a TPU *topology description* happens on a CPU host where the live check would
bake interpret=True into the lowered program, so ``compiled_kernels()``
overrides it for that path.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from contextvars import ContextVar
from pathlib import Path

#: ContextVar, NOT a module global: the override must be invisible to other
#: threads (a server warmup tracing an engine while an AOT compile runs would
#: otherwise bake interpret=False into its jit cache and crash on CPU later).
_FORCE_COMPILED: ContextVar[bool] = ContextVar("force_compiled_kernels",
                                               default=False)

#: where compiled programs persist when JAX_COMPILATION_CACHE_DIR is unset:
#: one fixed, git-ignored path inside the checkout. The directory is part of
#: what a later process must agree on to hit, so it is never derived from a
#: temporary name, a pid or the time.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when the first device is a TPU, False on the CPU backend. Any
    other platform is an error: running it would pick interpret-mode kernels
    and TPU sizes at once and label the result a chip number."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "tpu":
        return True
    if platform == "cpu":
        return False
    raise RuntimeError(
        f"unsupported JAX platform {platform!r}: this code runs on 'tpu' "
        "(the chip) or 'cpu' (tests and rehearsals) only")


def require_cpu(what: str) -> None:
    """For a harness that starts worker children pinned to the CPU beside
    work in its own process: a parent that holds a TPU would compare two
    devices, and keep the chip from any child that needed it. Refuse."""
    if on_tpu():
        raise RuntimeError(
            f"{what} is a CPU harness (its children run with "
            "JAX_PLATFORMS=cpu) and this process holds a TPU backend; run it "
            "with JAX_PLATFORMS=cpu")


def default_interpret() -> bool:
    """True → pallas interpret mode (no Mosaic). False on a TPU and inside
    ``compiled_kernels()`` (AOT lowering for a TPU topology)."""
    if _FORCE_COMPILED.get():
        return False
    return not on_tpu()


@contextmanager
def compiled_kernels():
    """Force pallas kernels to lower as real Mosaic kernels even though the
    live backend is not a TPU — used when tracing/lowering against a TPU
    topology description (runtime/aot_tpu.py). Scoped to the current context
    (thread/task), so concurrent tracing elsewhere keeps CPU semantics."""
    token = _FORCE_COMPILED.set(True)
    try:
        yield
    finally:
        _FORCE_COMPILED.reset(token)


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
    and no directory is set in code; otherwise ``COMPILE_CACHE_DIR``, on a
    TPU. On the CPU backend nothing is set: its compiles are small, and
    XLA:CPU's loader reports a machine-feature mismatch for every entry it
    reads back. Returns the directory in use, if any."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if not on_tpu():
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
