"""Static-analysis audit of the repo's structural contracts.

The paper's claim — inference "without the use of any multipliers" — is a
property of the *program*, not of any particular run.  This package
proves it (and the layout, donation, and plan-consistency contracts that
keep it cheap) by tracing the jitted serving steps for a committed matrix
of model configs x table families and checking rules on the closed jaxpr
and compiled HLO, with nothing executed:

* :func:`iter_eqns` / :func:`op_census` — the one recursive jaxpr walker
  (scan/while/cond/jit/custom-vjp/remat; ``pallas_call`` stays opaque)
* :func:`multiplier_free_violations`, :func:`zero_copy_violations`,
  :func:`plan_consistency_violations`, :func:`donation_violations`,
  :func:`overflow_violations` — the rule classes (empty list == invariant
  holds)
* :func:`interval_eval` / :func:`layer_range_cert` /
  :func:`precision_report` — the range/overflow pass: interval abstract
  interpretation over the traced steps plus closed-form per-plan
  accumulator and error-bound certificates
* :data:`AUDIT_POINTS` / :func:`audit_point` / :func:`trace_point` — the
  audited matrix (one shared abstract trace per point)
* :func:`build_manifest` & friends — the JSON manifest behind
  ``python -m repro.audit --check`` (the CI gate) and ``--write``

See "Audited invariants" in ``src/repro/core/README.md`` for the rule
table.
"""
from repro.audit.compiled import (
    aliased_param_indices,
    compiled_report,
    donation_violations,
)
from repro.audit.interp import (
    INT_INPUT_BOUND,
    Interval,
    OverflowFact,
    default_arg_intervals,
    dtype_interval,
    interval_eval,
)
from repro.audit.manifest import (
    ManifestError,
    build_manifest,
    diff_manifests,
    load_manifest,
    manifest_violations,
    write_manifest,
)
from repro.audit.points import (
    AUDIT_POINTS,
    AuditPoint,
    audit_point,
    build_point,
    trace_point,
)
from repro.audit.ranges import (
    RangeCert,
    layer_range_cert,
    overflow_violations,
    pallas_interval_model,
    precision_report,
)
from repro.audit.rules import (
    Violation,
    multiplier_free_violations,
    plan_consistency_violations,
    planned_weight_shapes,
    table_leaf_shapes,
    zero_copy_violations,
)
from repro.audit.walker import OPAQUE_PRIMITIVES, as_eqns, iter_eqns, op_census

__all__ = [
    "AUDIT_POINTS",
    "AuditPoint",
    "INT_INPUT_BOUND",
    "Interval",
    "ManifestError",
    "OPAQUE_PRIMITIVES",
    "OverflowFact",
    "RangeCert",
    "Violation",
    "aliased_param_indices",
    "as_eqns",
    "audit_point",
    "build_manifest",
    "build_point",
    "compiled_report",
    "default_arg_intervals",
    "diff_manifests",
    "donation_violations",
    "dtype_interval",
    "interval_eval",
    "iter_eqns",
    "layer_range_cert",
    "load_manifest",
    "manifest_violations",
    "multiplier_free_violations",
    "op_census",
    "overflow_violations",
    "pallas_interval_model",
    "plan_consistency_violations",
    "planned_weight_shapes",
    "precision_report",
    "table_leaf_shapes",
    "trace_point",
    "write_manifest",
    "zero_copy_violations",
]
