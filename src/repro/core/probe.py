"""Compiler-guided probes: derive a task's ResourceVector from the XLA
compiled artifact — the JAX analogue of the paper's instrumented
``task_begin(mem, threads, blocks)``.

Paper §III-A3: the LLVM pass interprets symbolic cudaMalloc sizes / grid dims
at runtime. Here the "compiler" is XLA itself: ``jit(fn).lower(args)`` +
``.compile()`` yield the exact HBM footprint (memory_analysis) and the
FLOP/byte work (cost_analysis) of the whole computation — the task is already
a closed, device-independent unit, so the analysis is exact rather than a
static over-approximation.

``probe_fn`` is cached by (fn, shapes): the paper amortizes its static
analysis at compile time; we amortize the AOT lowering the same way.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import SingleDeviceSharding

from repro.core.chips import device_peaks
from repro.core.task import ResourceVector
from repro.obs.spans import span


def _mem_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(getattr(m, "argument_size_in_bytes", 0)
               + getattr(m, "output_size_in_bytes", 0)
               + getattr(m, "temp_size_in_bytes", 0)
               - getattr(m, "alias_size_in_bytes", 0))


def _cost(compiled) -> Dict[str, float]:
    c = compiled.cost_analysis()
    if isinstance(c, list):
        c = c[0]
    return c or {}


def vector_from_compiled(compiled, *, chips: int = 1,
                         flops_override: Optional[float] = None,
                         collective_bytes: float = 0.0,
                         work_scale: float = 1.0,
                         efficiency: Tuple[float, float] = (1.0, 1.0)
                         ) -> ResourceVector:
    """Build the probe payload from a compiled executable.

    ``flops_override`` replaces XLA's flops counter (which counts while-loop
    bodies once — see launch.flops) with an analytic model when available.
    ``work_scale`` multiplies duration terms (e.g. a job = N identical steps).

    ``efficiency`` = (core_eff, bw_eff): the fraction of peak compute / HBM
    bandwidth the kernel ACHIEVES while running solo. The roofline terms bound
    a perfect kernel; real ones sit below the roof (occupancy, latency,
    divergence — the paper's own motivation cites ~30% typical utilization),
    and the achieved fraction is exactly the resource share a co-resident
    consumes. Callers pass measured/calibrated profiles (workloads.py) or
    leave (1, 1) for ideal kernels.
    """
    pk = device_peaks()
    cost = _cost(compiled)
    flops = float(flops_override if flops_override is not None
                  else cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    core_eff, bw_eff = efficiency
    compute_s = flops / (chips * pk.flops * core_eff)
    memory_s = bytes_acc / (pk.hbm_bw * bw_eff)
    collective_s = collective_bytes / pk.ici_bw
    est = max(compute_s, memory_s, collective_s, 1e-9)
    # demands: achieved share of the raw roof, per wall-second
    compute_share = (flops / (chips * pk.flops)) / est
    memory_share = (bytes_acc / pk.hbm_bw) / est
    return ResourceVector(
        hbm_bytes=_mem_bytes(compiled),
        flops=flops * work_scale,
        bytes_accessed=bytes_acc * work_scale,
        collective_bytes=collective_bytes * work_scale,
        est_seconds=est * work_scale,
        # fraction of the chip's compute-seconds (resp. HBM-bandwidth-seconds)
        # this task occupies per wall-second while running: a compute-bound
        # kernel at 85% MXU efficiency has core_demand 0.85
        core_demand=max(min(compute_share, 1.0), 0.01),
        bw_demand=max(min(memory_share, 1.0), 0.01),
        chips=chips,
    )


_probe_cache: Dict[Tuple, Any] = {}


def _abstractify(tree):
    """Shapes of ``tree`` with the shardings the probed call will see: a
    committed array (or a ShapeDtypeStruct with a sharding) keeps its own,
    anything else is taken to run on the default device. The probe then
    lowers the very program a call committed to that device runs, and the
    call reuses the probe's compile instead of compiling again."""
    default = SingleDeviceSharding(jax.devices()[0])

    def leaf(a):
        sh = a.sharding if isinstance(a, jax.ShapeDtypeStruct) \
            or getattr(a, "committed", False) else None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh or default)
    return jax.tree_util.tree_map(leaf, tree)


def probe_fn(fn: Callable, *args, chips: int = 1, work_scale: float = 1.0,
             flops_override: Optional[float] = None,
             efficiency: Tuple[float, float] = (1.0, 1.0)) -> ResourceVector:
    """Probe a python/jitted function with concrete or abstract args (any
    pytree of arrays/ShapeDtypeStructs).

    This is the instrumented ``task_begin`` of the paper: called right before
    launch, it conveys the resource needs to the scheduler. AOT compilation
    happens once per (fn, shape-signature). A ``jax.jit``-wrapped ``fn`` is
    lowered as it stands, so its donations (aliased outputs) count as they
    will when it runs.
    """
    with span("repro.probe") as sp:
        sds = _abstractify(args)
        leaves, treedef = jax.tree_util.tree_flatten(sds)
        key = (id(fn), treedef,
               tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
        compiled = _probe_cache.get(key)
        sp.set_metadata(hit=int(compiled is not None))
        if compiled is None:
            jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
            compiled = jitted.lower(*sds).compile()
            if len(_probe_cache) < 512:
                _probe_cache[key] = compiled
        return vector_from_compiled(compiled, chips=chips,
                                    work_scale=work_scale,
                                    flops_override=flops_override,
                                    efficiency=efficiency)
