"""What a traffic driver and the per-layer readers share about one run
of one cell: its files, its seed, the benchmark's own spans, and the
counters and zones summed over the nodes the window drove."""

import math


class Spans:
    """The benchmark's own spans round its calls into the program:
    (name, start, end, args) on `time.perf_counter`."""

    def __init__(self):
        self.items = []

    def add(self, name: str, start: float, end: float, **args) -> None:
        self.items.append((name, start, end, args))

    def total(self, name: str) -> float:
        return sum(e - s for n, s, e, _ in self.items if n == name)

    def named(self, name: str) -> list:
        return [(s, e, a) for n, s, e, a in self.items if n == name]


class Cell:
    def __init__(self, name, config, traffic, seed, seconds, trace,
                 workdir, chips):
        self.name = name
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.workdir = workdir
        self.chips = chips
        self.spans = Spans()
        self.counters = {}        # name -> (count, sum) inside the window
        self.zones = {}           # zone -> (count, seconds) inside it
        self.traffic_counts = {}  # transactions, signatures, ledgers
        self.window = (0.0, 0.0)  # perf_counter start and end
        self.notes = []
        self.recorders = []       # FlightRecorders of the watched nodes
        self.device_trace = None  # harness.trace.DeviceTrace, --trace 1

    def note(self, text: str) -> None:
        self.notes.append(text)

    def watch_app(self, app) -> None:
        """In a traced run, switch the node's FlightRecorder on so its
        zones can name the device's idle gaps."""
        if self.trace:
            app.flight_recorder.start(capacity=1 << 21)
            self.recorders.append(app.flight_recorder)

    @staticmethod
    def percentile(values, q: float) -> float:
        """Nearest-rank percentile of all `values`."""
        if not values:
            raise ValueError("no samples")
        ordered = sorted(values)
        rank = max(1, math.ceil(q / 100.0 * len(ordered)))
        return ordered[rank - 1]
