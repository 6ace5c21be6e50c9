"""dplint fixture — DPL011 clean: telemetry carries operational
aggregates and fully released statistics only.

``spec`` is a resolved budget_accounting.MechanismSpec.
"""

import time

from pipelinedp_tpu import noise_core
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.ops import columnar


def record_released_stat(key, pid, pk, value, spec, n, span):
    accs = columnar.bound_and_aggregate(key, pid, pk, value,
                                        num_partitions=n)
    # Bounded AND noised: a released statistic may enter telemetry.
    noised = noise_core.add_laplace_noise_array(accs, 1.0 / spec.eps)
    span.set_attribute("released_total", float(noised))


def record_operational_metrics(histogram, n_chunks):
    # Timings and structural counts are operational, not private.
    t0 = time.perf_counter()
    with obs_trace.span("dp/stream_slab_0", chunk0=0, chunk1=n_chunks):
        pass
    histogram.observe(time.perf_counter() - t0)


def record_row_count_metadata(n_rows, span):
    # Plain operational scalars (row counts arriving as config, not
    # derived from a private column) never taint.
    span.set_attribute("n_rows", int(n_rows))
