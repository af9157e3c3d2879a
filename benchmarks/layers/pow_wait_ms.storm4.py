"""send queue: as ``pow_wait_ms`` (request to nonce as the sender sees it,
``worker_pow_wait_seconds`` grown in the window), where eight groups
take their turns on four chips."""

from benchmarks.layers import _twin

read = _twin.of("pow_wait_ms")
