"""time_to_solution_s: the window's wall time over the calls completed in
it -- seconds per checked solution (host clock)."""


def read(run):
    if run.get("trace") is not None or not run["calls"]:
        return None
    return run["window_s"] / run["calls"]
