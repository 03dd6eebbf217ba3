"""The gradient kernels' share of the chip's HBM bandwidth, in %: the least
bytes the window's oracle evaluations had to move (:func:`least_bytes`,
per evaluation) over the device seconds of the gradient kernels
(``_dense_kernel*`` and ``_compact_kernel*`` among the trace's largest
device operations), over the chip's peak.  Nothing where the trace names no
gradient kernel or the chip is not one whose peak is known."""

HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}    # Google Cloud documentation, "TPU v5e"
KERNELS = ("_dense_kernel", "_compact_kernel")


def least_bytes(m: int, n: int, g: int, live_blocks: float) -> float:
    """Bytes one evaluation of the group-sparse dual's gradient must move at
    the least: the float32 cost entries of the class blocks it cannot skip
    (``live_blocks`` blocks of ``g`` rows by one column), the duals in
    (``m + n``) and their gradients out (``m + n``).  Counted from the shape
    and the screening's verdicts, not from a kernel: a kernel that reads a
    block it could have skipped moves more than this."""
    return 4.0 * (g * live_blocks + 2 * (m + n))


def read(run, reduced):
    peak = HBM_BYTES_PER_S.get(run.devices[0].device_kind)
    solves = run.facts.get("window_solves")
    seconds = sum(s for name, s in reduced.breakdown.get("device_ops", [])
                  if name.startswith(KERNELS))
    if not peak or not solves or seconds <= 0:
        return None
    cfg = run.config
    g, n = cfg["samples_per_class"], cfg["num_target"]
    m = cfg["num_classes"] * g
    least = sum(steps * c["evals"] * least_bytes(m, n, g, c["live_blocks"] / max(c["rounds"], 1))
                for steps, c in solves)
    return 100.0 * least / seconds / peak
