"""How the package reaches the device: where compiled programs are kept
between processes, and one compile per argument shape that can be timed
and inspected.

Every device program in the package (the columnar decode, the sharded
decode and its statistics, the device aggregate) is a `DeviceProgram`.
It compiles ahead of time, once per argument shape and under a lock —
scan threads that share one decoder wait for the first compile instead
of each repeating it — and keeps for every shape what a caller cannot
get from a bare `jax.jit`: the seconds the compile took, how many of them
went to tracing and lowering, and whether the compiled program holds the
fused Pallas kernel.
"""
from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

import jax

from ..profiling import Stage

# the Mosaic kernel's custom-call target in compiled HLO; absent when the
# Pallas kernel ran through the interpreter or no group was fused
KERNEL_CALL_TARGET = "tpu_custom_call"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home before the first
    device program is built; returns the directory in use.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the operator's choice and
    JAX has already read it: nothing is touched. Otherwise the cache
    lives at `<checkout>/.jax_cache` — a fixed path, because the path is
    part of the cache key and a directory that moves never hits."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclass(frozen=True, slots=True)
class CompiledShape:
    """One compiled executable with what was learned building it."""

    executable: object
    # trace + lower + compile (or the load from the persistent cache),
    # and the part of it spent tracing and lowering, which no cache keeps
    compile_s: float
    lower_s: float
    has_kernel: bool


def _shape_key(args) -> Tuple:
    # shape/dtype attributes only: np.asarray on a device array would
    # copy it to the host just to read its type
    return tuple((tuple(a.shape), np.dtype(a.dtype).name) for a in args)


class DeviceProgram:
    """`jax.jit(fn, **jit_options)` compiled ahead of time per argument
    shape. `interpreted` says whether the program's Pallas kernel runs
    through the interpreter (None: it has no Pallas kernel);
    `device_groups` how many of the decode's kernel groups took which
    route ({"fused", "fused_rows_in_lanes", "sliced", "gathered"}, and
    "points_u8" for a read's program that returns a matrix of code
    points: build_jax_decode_fn, ColumnarDecoder._read_program); `points` where the decode's EBCDIC string
    columns lie in that matrix (columnar.StringPoints; None: the
    program returns none)."""

    def __init__(self, fn, interpreted: Optional[bool] = None,
                 device_groups: Optional[Dict[str, int]] = None,
                 points=None, **jit_options):
        self.interpreted = interpreted
        self.device_groups = device_groups
        self.points = points
        self._jit = jax.jit(fn, **jit_options)
        self._lock = threading.Lock()
        self._compiled: Dict[Tuple, CompiledShape] = {}

    def compiled_for(self, *args) -> Tuple[CompiledShape, bool]:
        """(the executable for these arguments' shapes, whether this
        call built it)."""
        key = _shape_key(args)
        entry = self._compiled.get(key)
        if entry is not None:
            return entry, False
        # threads that find the lock taken wait for the same compile: the
        # stage covers the wait too
        with Stage("compile"), self._lock:
            entry = self._compiled.get(key)
            if entry is not None:
                return entry, False
            ensure_compile_cache()
            t0 = time.perf_counter()
            lowered = self._jit.lower(*[
                jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in key])
            t1 = time.perf_counter()
            executable = lowered.compile()
            entry = CompiledShape(
                executable, time.perf_counter() - t0, t1 - t0,
                KERNEL_CALL_TARGET in executable.as_text())
            self._compiled[key] = entry
            return entry, True

    def __call__(self, *args):
        entry, _ = self.compiled_for(*args)
        return entry.executable(*args)
