"""``service``: one ``CompressionService`` with its default
configuration."""
from typing import Any, Dict, List


class Service:
    """Requests go to the service's queue; ``submit`` returns a future,
    so several may be in flight at once."""

    def __init__(self, program):
        self.svc = program.CompressionService()

    def submit(self, op: str, item):
        if op == "compress":
            f, xi = item
            return self.svc.submit_compress(f, xi)
        return self.svc.submit_decompress(item)

    def call(self, op: str, item):
        return self.submit(op, item).result()

    def _burst(self, op: str, chunk: List) -> None:
        for fut in [self.submit(op, it) for it in chunk]:
            fut.result()

    def warm(self, op: str, items: List, traffic: Dict[str, Any], counter,
             rounds: int = 4) -> None:
        """Compile, before the window, every batch size and shape the
        window will send.

        A traffic of ``step``s sends only whole steps of the first
        ``step`` items: one burst each of 4, 2 and 1 of them (every
        padded batch size up to the default ``max_batch`` of 4), then one
        whole step, which forms the batches the window's steps form.

        Any other traffic: every item once, in bursts of 4, then bursts
        of 4, 2 and 1 of the first items until a round lowers nothing
        (``counter.n`` unchanged; ``rounds`` at most)."""
        if "step" in traffic:
            for size in (4, 2, 1):
                self._burst(op, items[:size])
            self._burst(op, items[:int(traffic["step"])])
            return
        for s in range(0, len(items), 4):
            self._burst(op, items[s:s + 4])
        for _ in range(rounds):
            before = counter.n
            for size in (4, 2, 1):
                self._burst(op, items[:size])
            if counter.n == before:
                break

    def stats(self) -> Dict[str, Any]:
        return self.svc.stats()

    def close(self) -> None:
        self.svc.close()


def start(program) -> Service:
    return Service(program)
