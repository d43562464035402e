"""matmul_roofline.linalg: the call's nominal operations per chip over its
matrix-multiply device seconds, as a share of the chip's bfloat16 peak (%).

The nominal count is LAPACK's and BLAS's (bench/entries), so the share
reads the same work whatever implements it. float32 at full precision runs
as several bfloat16 passes, so float32 cells read low against this peak."""


def read(run):
    tr = run.get("trace")
    if not tr or not run["calls"] or tr["class_s"]["matmul"] <= 0:
        return None
    per_call = tr["class_s"]["matmul"] / run["calls"]
    flops = run["work"]["flops"] / run["chips"]
    return 100.0 * flops / per_call / run["peak"]["bf16_flops_per_s"]
