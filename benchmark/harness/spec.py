"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file found
by its name, so a later PR adds files and entries and edits nothing."""

import importlib.util
import json
import os


class Spec:
    def __init__(self, root: str, doc: dict):
        self.root = root
        self.doc = doc
        self.dir = os.path.join(root, doc["paths"][0])

    @classmethod
    def load(cls, root: str) -> "Spec":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            return cls(root, json.load(f))

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        """`traffic/<name>.json`: {"generator": ..., "params": {...}}."""
        with open(os.path.join(self.dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def _module(self, sub: str, name: str):
        path = os.path.join(self.dir, sub, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{sub}_{name}".replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def generator(self, name: str):
        return self._module("generators", name)

    def layer_reader(self, name: str):
        return self._module("layer_metrics", name).read

    def kernel_cost(self, program: str):
        return self._module("kernel_costs", program)

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.dir, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           "peaks.json: add it with its source, there is "
                           "no default")
        return table[device_kind]

    def metrics_for(self, group: str, workload: str, reports: list) -> list:
        """Entries of `end_to_end` / `per_layer` that this cell carries:
        those that list it, and those that list nothing and move (or
        are) an end-to-end metric the cell reports."""
        out = []
        for m in self.doc[group]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif group == "end_to_end" or m["moves"] in reports:
                out.append(m)
        return out
