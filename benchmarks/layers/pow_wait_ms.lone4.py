"""send queue: as ``pow_wait_ms`` (request to nonce as the sender sees
it, ``worker_pow_wait_seconds`` grown in the window), where the request
is one object alone and four chips search for it: what the share-out
is for."""

from benchmarks.layers import _twin

read = _twin.of("pow_wait_ms")
