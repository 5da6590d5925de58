"""The program's own spans and the device ops' self time, from a profiler
trace.

``load`` reads a trace as ``trace.load`` does and adds

    "spans": [[name, start_ns, end_ns, thread, args], ...]
        # host events named repro.* (the program's spans, repro.obs.spans)
    "harness_thread": the thread of the "bench.traced" span

A thread is a host line's name and its index in the plane (threads of one
process can share a name). The functions below reduce that dict:

* self time: a span's or a device op's duration less the part that the
  events nested in it (same thread, same device line) cover;
* ``idle_by_label``: every idle stretch of the first device, summed by what
  the host was doing at its middle;
* ``decode_scopes``: the decode program's op self time by the model's
  named scope, each op joined by its instruction name with the compiled
  program's HLO text and its ``op_name`` metadata (``hlo_scopes``);
* the span readings a metric reads (``probe_ms``, ``sched_call_ms``,
  ``pump_host_ms``, ``gc_pause_share``).

``test_spantrace.py`` checks them against brute force and on a trace of
the engine recorded on a TPU v5e (``span_report.py --record``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import trace as TRC

SPAN_PREFIX = "repro."
# the model's named scopes (repro.models.decode.decode_step); an op outside
# all of them is the layer scans' own work
MODEL_SCOPES = ("embed", "norm", "mamba", "state_write", "attn", "kv_write",
                "mlp", "logits")
PLUMBING = "scan plumbing"
UNMAPPED = "not in the HLO text"


def xplane(path: str) -> str:
    """The newest .xplane.pb under a profiler directory (or ``path``)."""
    if not os.path.isdir(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> dict:
    """``trace.load(path)`` with the program's spans added."""
    from jax.profiler import ProfileData
    path = xplane(path)
    tr = TRC.load(path)
    spans, harness = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"
            for e in line.events:
                if e.name == TRC.WINDOW_SPAN:
                    harness = thread
                elif e.name.startswith(SPAN_PREFIX):
                    spans.append([e.name, e.start_ns, e.end_ns, thread,
                                  {k: v for k, v in e.stats}])
    tr["spans"] = sorted(spans, key=lambda s: (s[1], -s[2]))
    tr["harness_thread"] = harness
    return tr


# -- self time -------------------------------------------------------------

def _covered(iv: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in TRC._union(iv))


def self_times(events: Sequence[list]) -> List[float]:
    """For events of one thread (or one device line), ``[name, start,
    end, ...]``: each one's duration less what the events nested in it
    cover, in the events' order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    kids: Dict[int, List[Tuple[float, float]]] = {i: [] for i in order}
    stack: List[int] = []
    for i in order:
        s, e = events[i][1], events[i][2]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            kids[stack[-1]].append((s, min(e, events[stack[-1]][2])))
        stack.append(i)
    return [events[i][2] - events[i][1] - _covered(kids[i])
            for i in range(len(events))]


def span_self(tr: dict) -> List[float]:
    """Self time (ns) of each span of ``tr["spans"]``, in its order."""
    out = [0.0] * len(tr["spans"])
    for thread in {s[3] for s in tr["spans"]}:
        idx = [i for i, s in enumerate(tr["spans"]) if s[3] == thread]
        for i, v in zip(idx, self_times([tr["spans"][i] for i in idx])):
            out[i] = v
    return out


def outermost(spans: Sequence[list], prefix: str) -> List[list]:
    """Spans named ``prefix``* that no other such span on their thread
    holds (a shrink's drain may grow a waiting slot inside it)."""
    ours = sorted((s for s in spans if s[0].startswith(prefix)),
                  key=lambda s: (s[3], s[1], -s[2]))
    out, end = [], {}
    for s in ours:
        if s[1] >= end.get(s[3], float("-inf")):
            out.append(s)
            end[s[3]] = s[2]
        else:
            end[s[3]] = max(end[s[3]], s[2])
    return out


def in_window(tr: dict, name: Optional[str] = None) -> List[list]:
    """Spans (named ``name``, if given) that start inside the window."""
    lo, hi = tr["window"]
    return [s for s in tr["spans"] if lo <= s[1] < hi
            and (name is None or s[0] == name)]


# -- idle time of the device, by what the host was doing -------------------

def _nesting_index(spans: Sequence[list]):
    """For the spans of one thread: a function of time that returns the
    innermost one open then (or None). Spans of one thread nest, so it is
    the last to start before ``t`` or, if that one has ended, the nearest
    of its enclosing spans that has not."""
    ss = sorted(spans, key=lambda s: (s[1], -s[2]))
    starts = [s[1] for s in ss]
    parent, stack = [], []
    for i, s in enumerate(ss):
        while stack and ss[stack[-1]][2] <= s[1]:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)

    def innermost(t):
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and ss[i][2] < t:
            i = parent[i]
        return ss[i] if i >= 0 else None
    return innermost


def labeler(tr: dict):
    """``host_label`` for many times of one trace, its spans indexed once."""
    threads: Dict[str, List[list]] = {}
    for s in tr.get("spans", []):
        threads.setdefault(s[3], []).append(s)
    index = {th: _nesting_index(ss) for th, ss in threads.items()}
    harness = index.pop(tr.get("harness_thread"), None)
    bench = _nesting_index(tr["host"])

    def label(t: float) -> str:
        best = harness(t) if harness else None
        if best is None:
            found = [s for s in (f(t) for f in index.values()) if s]
            best = min(found, key=lambda s: s[2] - s[1], default=None)
        if best is None:
            best = bench(t)
        return best[0] if best else "host-other"
    return label


def host_label(tr: dict, t: float) -> str:
    """The innermost repro.* span open at ``t``: on the harness thread
    first, else the shortest open on any thread; else the innermost bench.*
    span (``trace.host_label``), or "host-other"."""
    return labeler(tr)(t)


def idle_stretches(tr: dict) -> List[Tuple[float, float]]:
    """The window's stretches in which the first device ran nothing."""
    lo, hi = tr["window"]
    if not tr["devices"]:
        return []
    busy = TRC._busy_intervals(next(iter(tr["devices"].values())), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_by_label(tr: dict) -> Dict[str, float]:
    """Seconds of the first device's idle time in the window, summed by
    the host label at each idle stretch's middle, largest first."""
    acc: Dict[str, float] = {}
    label = labeler(tr)
    for s, e in idle_stretches(tr):
        k = label((s + e) / 2)
        acc[k] = acc.get(k, 0.0) + (e - s) * 1e-9
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def idle_gaps(tr: dict, k: int = 10) -> List[list]:
    """``trace.idle_gaps`` with the program's spans as labels."""
    gaps = sorted(idle_stretches(tr), key=lambda g: g[0] - g[1])[:k]
    label = labeler(tr)
    return [[label((s + e) / 2), (e - s) * 1e-9] for s, e in gaps]


# -- the decode program's time by named scope ------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?<![\w.%\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r"op_name=\"([^\"]*)\"")
# ops that only move or regroup their operands: a fusion is named by what
# they carry
_THROUGH = {"bitcast", "tuple", "get-tuple-element", "copy", "reshape"}


def parse_hlo(hlo_text: str) -> Dict[str, dict]:
    """{computation: {"root": name, "ops": {name: (opcode, operands,
    callee, op_name)}}} from a compiled program's HLO text."""
    comps: Dict[str, dict] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), {"root": None, "ops": {}})
            continue
        m = _INSTRUCTION.match(line)
        if not m or cur is None:
            continue
        rest = m.group(3).split(", metadata=", 1)
        op = _OPCODE.search(rest[0])
        calls = re.search(r"calls=%([\w.\-]+)", rest[0])
        meta = _OP_NAME.search(rest[1]) if len(rest) > 1 else None
        operands = re.findall(r"(?<![=\w])%([\w.\-]+)",
                              rest[0][op.end():] if op else "")
        cur["ops"][m.group(2)] = (op.group(1) if op else "", operands,
                                  calls.group(1) if calls else None,
                                  meta.group(1) if meta else "")
        if m.group(1):
            cur["root"] = m.group(2)
    return comps


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> model scope (or PLUMBING) for the instructions
    of a compiled program's HLO text. An instruction takes the innermost
    model scope of its op_name. A fusion takes that of the work it fuses,
    found from the fused root through ops that only move data: XLA names a
    fusion by one of its instructions, which may be a scan's stacking
    bitcast. A copy outside a fusion stays plumbing."""
    comps = parse_hlo(hlo_text)

    def fused(comp, name):
        opcode, operands, callee, op_name = comps[comp]["ops"][name]
        if opcode == "fusion" and comps.get(callee, {}).get("root"):
            return fused(callee, comps[callee]["root"])
        if opcode in _THROUGH:
            for o in operands:
                if o in comps[comp]["ops"]:
                    found = fused(comp, o)
                    if found:
                        return found
            return None
        scope = scope_of(op_name)
        return None if opcode == "parameter" or scope == PLUMBING else scope

    out: Dict[str, str] = {}
    for comp in comps.values():
        for name, (opcode, _, callee, op_name) in comp["ops"].items():
            found = None
            if opcode == "fusion" and comps.get(callee, {}).get("root"):
                found = fused(callee, comps[callee]["root"])
            out[name] = found or scope_of(op_name)
    return out


def scope_of(op_name: str) -> str:
    """The innermost model scope on an op_name's path, or PLUMBING."""
    for part in reversed(op_name.split("/")):
        if part in MODEL_SCOPES:
            return part
    return PLUMBING


def module_op_self(tr: dict, module_prefix: str) -> Dict[str, float]:
    """Seconds of op self time by instruction name, over the ops that start
    inside the window's ``module_prefix`` modules (first device)."""
    lo, hi = tr["window"]
    if not tr["devices"]:
        return {}
    dev = next(iter(tr["devices"].values()))
    mods = sorted((s, e) for name, s, e in dev["modules"]
                  if name.startswith(module_prefix) and e > lo and s < hi)
    starts = [s for s, _ in mods]
    acc: Dict[str, float] = {}
    for (name, s, e), v in zip(dev["ops"], self_times(dev["ops"])):
        j = bisect.bisect_right(starts, s) - 1
        if j < 0 or s >= mods[j][1] or not lo <= s < hi:
            continue
        k = TRC.op_name(name)
        acc[k] = acc.get(k, 0.0) + v * 1e-9
    return acc


def decode_scopes(tr: dict, hlo_text: str, module_prefix: str
                  ) -> Dict[str, float]:
    """Seconds of op self time inside the window's ``module_prefix``
    modules by model scope (first device); ops whose instruction the HLO
    text lacks fall under UNMAPPED."""
    scopes = hlo_scopes(hlo_text)
    acc: Dict[str, float] = {}
    for instr, v in module_op_self(tr, module_prefix).items():
        k = scopes.get(instr, UNMAPPED)
        acc[k] = acc.get(k, 0.0) + v
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def decode_hlo_text(pc, params, rows: int, max_seq: int,
                    device) -> Optional[str]:
    """The compiled HLO text of the engine's decode program (``JaxModel``'s
    ``jit(_decode)``, whose trace module is ``jit__decode``) for ``rows``
    rows of ``max_seq`` positions on ``device``, from shapes alone;
    ``params`` may be shapes. None, without compiling, when the lowered
    program carries none of the model's scopes.

    It compiles past the persistent compilation cache: the cache's key
    leaves out op_name metadata, so a cached program that differs only in
    its scopes (an earlier version of the program) would come back with
    the other's metadata. The instructions the device trace names are the
    same either way."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from repro.models import decode as D
    from repro.serve.decode import abstract_cache
    on = SingleDeviceSharding(device)
    args = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=on),
        (params, abstract_cache(pc, rows, max_seq),
         jax.ShapeDtypeStruct((rows,), jnp.int32),
         jax.ShapeDtypeStruct((rows,), jnp.int32)))

    def _decode(params, cache, tokens, pos):
        return D.decode_step(params, pc, cache, tokens, pos)
    lowered = jax.jit(_decode).lower(*args)
    scoped = re.compile("/(%s)/" % "|".join(MODEL_SCOPES))
    if not scoped.search(lowered.as_text(debug_info=True)):
        return None
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def plumbing_share(scopes: Dict[str, float]) -> Optional[float]:
    """The scan plumbing's share (%) of the decode program's self time;
    None when no op carries a model scope (a program without the scopes)."""
    total = sum(scopes.values())
    if not total or not any(k in MODEL_SCOPES for k in scopes):
        return None
    return 100.0 * scopes.get(PLUMBING, 0.0) / total


# -- the span readings -----------------------------------------------------

def _mean_ms(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) * 1e-6 if xs else None


def probe_ms(tr: dict) -> Optional[float]:
    """Mean duration of the probes that start in the window."""
    return _mean_ms([e - s for _, s, e, _, _ in
                     in_window(tr, "repro.probe")])


def sched_call_ms(tr: dict) -> Optional[float]:
    """Mean duration of the outermost scheduler calls in the window."""
    return _mean_ms([e - s for _, s, e, _, _ in
                     outermost(in_window(tr), "repro.sched.")])


def pump_host_ms(tr: dict) -> Optional[float]:
    """Mean, over the window's pumps that stepped rows, of the pump's
    duration outside its wait for the device (readback)."""
    spans = in_window(tr)
    reads: Dict[str, List[list]] = {}
    for s in spans:
        if s[0] == "repro.serve.readback":
            reads.setdefault(s[3], []).append(s)
    xs = []
    for name, s, e, th, args in spans:
        if name == "repro.serve.pump" and args.get("rows", 0) > 0:
            inside = [(max(a, s), min(b, e)) for _, a, b, _, _
                      in reads.get(th, []) if b > s and a < e]
            xs.append(e - s - _covered(inside))
    return _mean_ms(xs)


def gc_pause_share(tr: dict) -> Optional[float]:
    """Share (%) of the window under a garbage collection; None for a
    trace without any program span."""
    if not tr["spans"]:
        return None
    lo, hi = tr["window"]
    gcs = [s[:3] for s in tr["spans"] if s[0] == "repro.gc"]
    return 100.0 * _covered(TRC._clip(gcs, lo, hi)) / (hi - lo)
