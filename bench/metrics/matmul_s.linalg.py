"""matmul_s.linalg: device seconds per call in matrix-multiply operations,
XLA's dot and convolution fusions and every Pallas kernel (device trace,
mean over the chips)."""


def read(run):
    tr = run.get("trace")
    if not tr or not run["calls"] or tr["class_s"]["matmul"] <= 0:
        return None
    return tr["class_s"]["matmul"] / run["calls"]
