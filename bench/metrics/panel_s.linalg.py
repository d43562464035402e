"""panel_s.linalg: device seconds per call in operations that are neither
matrix multiplies nor collectives -- the drivers' panels, row swaps and
triangular substitutions (device trace, mean over the chips)."""


def read(run):
    tr = run.get("trace")
    if not tr or not run["calls"]:
        return None
    return tr["class_s"]["other"] / run["calls"]
