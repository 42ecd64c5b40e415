"""The observability plane (DESIGN.md §14).

Opt-in recording and analysis over the netsim replay: a
:class:`~repro.obs.recorder.Recorder` attached to the simulator captures
every verb's exact service interval and queue/dependency decomposition
(pure post-hoc observation — recording off is bit-identical to today),
:mod:`repro.obs.export` renders runs as Chrome/Perfetto trace-viewer
JSON plus derived time series, :mod:`repro.obs.forensics` walks the
top-K slowest ops' dependency chains backwards into a four-component
latency attribution, and :mod:`repro.obs.metrics` folds everything into
the ``RunResult.obs`` registry.  :mod:`repro.obs.host` is the program's
own wall-clock trace: ``sherman.*`` profiler spans on the served path and
the counted device-to-host reads (DESIGN.md §14).
"""
from repro.obs.export import timeseries, to_chrome_trace, write_chrome_trace
from repro.obs.forensics import attribute_ops, span_accounting
from repro.obs.metrics import summarize
from repro.obs.recorder import Recorder, Segment

__all__ = ["Recorder", "Segment", "to_chrome_trace", "write_chrome_trace",
           "timeseries", "attribute_ops", "span_accounting", "summarize"]
