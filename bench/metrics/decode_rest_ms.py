"""Device time per decode step outside the table kernels: attention, the
cache's paged view and writes, norms, the head and sampling.  Kernel ops
are those named as ``lut_roofline.decode`` names them."""

from bench.metrics_common import kernel_ns_per_decode


def read(run):
    from bench import trace

    ex = trace.executions(run.events).get("jit_decode", [])
    kern = kernel_ns_per_decode(run)
    if not ex or kern is None:
        return None
    return (sum(e - s for s, e in ex) / len(ex) - kern) / 1e6
