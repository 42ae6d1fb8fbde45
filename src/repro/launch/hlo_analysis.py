"""Post-SPMD HLO analysis: collective traffic + roofline terms.

``compiled.as_text()`` is the *partitioned* (per-device) module, so all
sizes extracted here are per-chip.  Collective traffic uses the standard
ring-algorithm model over the op's replica-group size N:

  all-reduce       2 (N-1)/N * payload      (reduce-scatter + all-gather)
  all-gather       (N-1)/N * result bytes   (result = full gathered tensor)
  reduce-scatter   (N-1)/N * operand bytes  (operand = N * result)
  all-to-all       (N-1)/N * payload
  collective-permute  payload               (one hop per chip)

Hardware model: the published per-chip peaks of :data:`PEAKS`, looked up
by the ``device_kind`` JAX reports; a kind missing from the table raises.
"""
from __future__ import annotations

import dataclasses
import re

V5E = "TPU v5 lite"  # jax's device_kind for a TPU v5e chip

# Published per-chip peaks keyed by ``device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s inter-chip interconnect over 4 links.
PEAKS = {
    V5E: {
        "bf16_flops": 197e12,  # FLOP/s
        "int8_ops": 393e12,  # OP/s
        "hbm_bw": 819e9,  # B/s
        "hbm_bytes": 16e9,
        "link_bw": 50e9,  # B/s per ICI link
    },
}


def peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z]+[0-9a-z]*)\[([\d,]*)\]")
# "%name = TYPE op-name(" where TYPE may be a tuple
_OP_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))  # [G,N] = G groups of N
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclasses.dataclass
class CollectiveStats:
    by_op: dict
    result_bytes: int  # raw sum of collective result sizes
    link_bytes: float  # ring-model bytes through each chip's links

    def to_dict(self):
        return {
            "by_op": self.by_op,
            "result_bytes": self.result_bytes,
            "link_bytes": self.link_bytes,
        }


def collective_stats(hlo_text: str) -> CollectiveStats:
    by_op: dict[str, dict[str, float]] = {}
    total_result = 0
    total_link = 0.0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":  # -start carries the payload; skip the done
            continue
        type_str, op = m.group(1), m.group(2)
        payload = _type_bytes(type_str)
        n = max(_group_size(line), 1)
        ring = (n - 1) / n if n > 1 else 0.0
        if op == "all-reduce":
            link = 2 * ring * payload
        elif op == "all-gather":
            link = ring * payload  # result is the gathered tensor
        elif op == "reduce-scatter":
            link = ring * payload * n  # operand = N * result
        elif op == "all-to-all":
            link = ring * payload
        else:  # collective-permute
            link = float(payload)
        rec = by_op.setdefault(op, {"count": 0, "result_bytes": 0, "link_bytes": 0.0})
        rec["count"] += 1
        rec["result_bytes"] += payload
        rec["link_bytes"] += link
        total_result += payload
        total_link += link
    return CollectiveStats(by_op, total_result, total_link)


def roofline_terms(
    flops_per_device: float,
    hbm_bytes_per_device: float,
    link_bytes_per_device: float,
    device_kind: str = V5E,
) -> dict:
    """Seconds each resource needs at ``device_kind``'s bf16, HBM and ICI
    link peaks, the dominant one, and the roofline fraction."""
    pk = peaks(device_kind)
    compute = flops_per_device / pk["bf16_flops"]
    memory = hbm_bytes_per_device / pk["hbm_bw"]
    collective = link_bytes_per_device / pk["link_bw"]
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    terms["dominant"] = dominant.replace("_s", "")
    # roofline fraction: how much of the binding resource's time is the
    # compute we actually want (1.0 == perfectly compute-bound at peak)
    terms["roofline_fraction"] = compute / bound if bound > 0 else 0.0
    terms["device_kind"] = device_kind
    return terms


def model_flops(cfg, case) -> float:
    """MODEL_FLOPS: 6*N*D train / 2*N*D inference (N = active params)."""
    n_active = cfg.active_param_count()
    if case.kind == "train":
        tokens = case.global_batch * case.seq_len
        return 6.0 * n_active * tokens
    if case.kind == "prefill":
        tokens = case.global_batch * case.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * case.global_batch  # decode: one token per seq
