"""Event-sourced observability plane for the scheduler/executor stack.

One frozen event schema (``obs.events``) covers the full task lifecycle
across every scheduler class and both backends; a bounded lock-light
ring-buffer ``Tracer`` collects it with monotonic sequence numbers on the
backend's own timeline (wall monotonic live, virtual clock simulated).

  * ``obs.events``  — the schema, the ``Tracer``, and ``attach_tracer``
  * ``obs.explain`` — per-task decision-verdict rings (why parked, who
    evicted it, at what cost) and ``attach_explainer``
  * ``obs.export``  — Chrome/Perfetto trace-event JSON (device occupancy
    tracks, queue-depth counters, cross-device flow arrows, profiling
    counter tracks)
  * ``obs.metrics`` — log-bucketed histograms + counter/gauge registry
  * ``obs.profile`` — per-task observed-vs-predicted attribution joined
    from the event stream: runtime error, memory high-water vs reserved,
    queueing-delay decomposition, per-device occupancy timelines
  * ``obs.calibrate`` — online probe calibration: per-class EWMA runtime
    correction + safety-margin memory fed back into admission
    (``attach_calibrator`` / ``CalibratedScheduler``), never shrinking a
    reservation below the observed high-water
  * ``obs.replay``  — flight recorder + sim/live parity differ +
    lifecycle state-machine validator
  * ``obs.slo``     — rolling-window SLO burn rates, degradation alerts
    (the paper's 2.5% envelope, live), probe-drift alerts, Prometheus
    text exposition
  * ``obs.whatif``  — counterfactual replay of a recorded trace under
    alternate scheduler policies, with decision-level divergence diffs
  * ``obs.spans``   — ``span(name, **args)``: timing spans at the work
    sites (submit, probe, admission, dispatch, the decode pump's phases,
    garbage collection) as profiler annotations, on the device trace's
    clock; no-ops unless a ``jax.profiler`` session collects

The split: spans time the work on the device's clock; the ``Tracer``
logs the scheduler's decisions on the backend's own timeline.

The subsystem imports nothing from ``repro.core`` at module load so the
scheduler base can import it without cycles (``obs.whatif`` imports the
simulator lazily inside ``replay``), and a ``None`` tracer/explainer
keeps every emission site a single attribute load (the PR-6 hot-path
budget survives tracing disabled).
"""
from repro.obs import (  # noqa: F401
    calibrate, events, explain, export, metrics, profile, replay, slo,
    spans, whatif,
)
from repro.obs.calibrate import (  # noqa: F401
    CalibratedScheduler, CalibrationStore, attach_calibrator,
)
from repro.obs.events import Event, Tracer, attach_tracer  # noqa: F401
from repro.obs.explain import (  # noqa: F401
    Explainer, Verdict, attach_explainer, format_verdicts,
)
from repro.obs.profile import (  # noqa: F401
    Profiler, TaskProfile, device_occupancy, format_profile,
    profiles_from_events,
)
from repro.obs.spans import span  # noqa: F401
