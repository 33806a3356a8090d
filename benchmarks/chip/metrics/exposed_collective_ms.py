"""Collectives: per step, the time collective operations ran on a device
with no other operation running there, mean over the devices."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace["devices"] or not record.get("steps"):
        return None
    devs = trace["devices"].values()
    if not any(d["collective_ns"] for d in devs):
        return None
    exposed = sum(d["exposed_collective_ns"] for d in devs) / len(devs)
    return exposed / record["steps"] / 1e6
