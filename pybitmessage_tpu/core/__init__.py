"""Node: the explicit application object.

Replaces the reference's global-singleton wiring (bitmessagemain.py
Main.start + state.py/queues.py/shared.py) with one dependency-injected
object owning storage, network, and workers.

``Node`` is imported at its first use, not with the package: the
kernels' module (``ops/sha512_pallas.py``) takes ``persisted_jit`` from
``core/programcache.py``, and ``core.node`` imports the workers, the
solvers and with them that module.
"""


def __getattr__(name):
    if name == "Node":
        from .node import Node
        return Node
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))
