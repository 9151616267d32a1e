"""A node's own start and end per replay (s): totals of the program's
`app.create` and `app.start` zones plus the durations of the
`app.shutdown` spans in the nodes' FlightRecorders (the zones are read
before shutdown, so shutdown reaches the benchmark as spans), over the
replays."""


def shutdown_seconds(recorder) -> float:
    total = 0.0
    began = {}
    for ev in recorder.to_chrome_trace()["traceEvents"]:
        if ev.get("name") != "app.shutdown":
            continue
        if ev["ph"] == "B":
            began[ev["tid"]] = ev["ts"]
        elif ev["ph"] == "E" and ev["tid"] in began:
            total += (ev["ts"] - began.pop(ev["tid"])) / 1e6
    return total


def read(cell):
    replays, create = cell.zones.get("app.create", (0, 0.0))
    if not replays:
        return None
    _, start = cell.zones.get("app.start", (0, 0.0))
    stop = sum(shutdown_seconds(r) for r in cell.recorders)
    return (create + start + stop) / replays
