"""collective_exposed_s.summa: device seconds per call in collective
operations during which no other operation ran on that device (device
trace, mean over the chips)."""


def read(run):
    tr = run.get("trace")
    if not tr or not run["calls"] or tr["class_s"]["collective"] <= 0:
        return None
    return tr["collective_exposed_s"] / run["calls"]
