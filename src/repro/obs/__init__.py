"""repro.obs — unified tracing + metrics for the whole stack.

One substrate observes every layer: the threaded WSP runtime (wave compute,
push flight, pull-gate waits, per-pull staleness samples audited against the
Plan's D), the parameter server (push apply, snapshots), the simulated
transport (per-link sends carrying modeled delay + bytes), the pipelined
wave schedule (per-stage tick/bubble tracks), the Engine surface
(fit/step/prefill/decode) and the continuous-batching Scheduler
(admit/refuse/prefill-group/decode-step/retire).

    from repro.obs import Tracer
    tr = Tracer()
    report = Engine(plan, tracer=tr).fit()
    tr.export("trace.json")            # Chrome trace JSON; open in Perfetto
    # report.telemetry: staleness histogram, bubble fraction, link stats

Everything accepts a disabled tracer (the NULL_TRACER singleton) and then
records nothing and allocates nothing on the hot path — instrumented code
never needs a None check, and an untraced run is bit-identical to a traced
one (tracing observes timing, never the data path).

Enabled spans also enter `jax.profiler.TraceAnnotation("<track>/<name>")`,
so a profiler trace names the host's time by the program's own layers;
spans nested on one thread record their `parent`, and `current_tracer()`
gives code without a tracer parameter the enclosing span's tracer.
"""
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import (NULL_SPAN, NULL_TRACER, Tracer,
                             current_tracer, emit_pipeline_ticks)

__all__ = [
    "Histogram", "MetricsRegistry", "NULL_SPAN", "NULL_TRACER", "Tracer",
    "current_tracer", "emit_pipeline_ticks",
]
