"""Share of device busy time in ops the trace categorises as
convolution or matmul, in %, first chip: self time of the ops whose
`hlo_category` (XLA's own, read from the xplane's event metadata by
benchmarks/harness/xplane_meta.py) is in `MXU_CATEGORIES` / busy time.
A convolution fusion includes the elementwise work fused into it, so
this is an upper bound on pure MXU time. A trace without the category
leaves nothing to read."""

from benchmarks.harness.trace_reduce import MXU_CATEGORIES


def read(facts: dict) -> float | None:
    dev = facts["trace"]["devices"][0]
    if not dev["category_ns"] or not dev["busy_ns"]:
        return None
    mxu = sum(dev["category_ns"].get(c, 0) for c in MXU_CATEGORIES)
    return 100.0 * mxu / dev["busy_ns"]
