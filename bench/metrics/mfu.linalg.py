"""mfu.linalg: nominal operations of the calls completed in the traced
window over that window and the chips' bfloat16 peak (%)."""


def read(run):
    tr = run.get("trace")
    if not tr or not run["calls"] or tr["window_s"] <= 0:
        return None
    done = run["work"]["flops"] * run["calls"]
    return 100.0 * done / tr["window_s"] / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
