"""A twin of a reader another cell already has: the same reading under
a name of its own, for a cell whose number must not be listed beside
the other's (the reader's own file and its entry stay as they are)."""

from benchmarks import harness


def of(name: str):
    """``read`` of the reader ``layers/<name>.py`` of this benchmark."""
    def read(window):
        return harness.load_module(window.bench.root, "layers",
                                   name).read(window)
    return read
