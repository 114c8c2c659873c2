"""``one_shot``: the program's one-shot entry points,
``compress_preserving_mss`` and ``decompress_preserving_mss``."""
from typing import Any, Dict, List


class OneShot:
    """One call at a time; no ``submit``, so only a loop that waits for
    each answer before the next request can drive it."""

    def __init__(self, program):
        self.p = program

    def call(self, op: str, item):
        if op == "compress":
            f, xi = item
            return self.p.compress_preserving_mss(f, xi)
        return self.p.decompress_preserving_mss(item)

    def warm(self, op: str, items: List, traffic: Dict[str, Any],
             counter) -> None:
        """Every item once: each shape the window calls has compiled."""
        for it in items:
            self.call(op, it)

    def close(self) -> None:
        pass


def start(program) -> OneShot:
    return OneShot(program)
