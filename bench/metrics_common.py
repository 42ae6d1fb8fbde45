"""What several per-layer metrics read alike."""
from __future__ import annotations

# The table kernels' ops in a TPU v5e trace, read by hand (PERF.md): a
# Pallas call appears as a custom call named after its kernel function,
# ``_lut_affine_padded.N``, ``_lut_affine_grouped_padded.N``,
# ``_lut_tl1_padded.N`` and ``_lut_tl1_grouped_padded.N``.  Only an op's
# own name is matched, not its operands'.
KERNEL_PATTERNS = ("_lut_affine", "_lut_tl1")


def kernel_ns_per_decode(run):
    """Device ns of table-kernel ops per execution of ``jit_decode``; None
    where the window holds no decode step or no kernel op."""
    from bench import trace

    ex = trace.executions(run.events).get("jit_decode", [])
    if not ex:
        return None
    ops = trace.ops_within(run.events, ex)
    ns = sum(e - s for n, s, e in ops if trace.matches(n, KERNEL_PATTERNS))
    return ns / len(ex) if ns else None
