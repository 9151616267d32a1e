"""One compared number beside its limit."""


class Check:
    __slots__ = ("what", "value", "limit", "at_least")

    def __init__(self, what: str, value, limit, at_least: bool = False):
        self.what = what.strip()
        self.value = value
        self.limit = limit
        self.at_least = at_least      # value must reach the limit

    @property
    def ok(self) -> bool:
        if self.at_least:
            return self.value >= self.limit
        return self.value <= self.limit

    def line(self) -> str:
        rel = ">=" if self.at_least else "<="
        return (f"check: {self.what}: {self.value} (limit {rel} "
                f"{self.limit}) {'ok' if self.ok else 'FAILED'}")
