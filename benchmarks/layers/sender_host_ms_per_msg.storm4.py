"""sender: as ``sender_host_ms_per_msg.queue`` (``sender.sign``,
``sender.encrypt`` and ``sender.publish`` spans inside the window, per
broadcast published), at some 110 broadcasts a second on one crypto
thread."""

from benchmarks.layers import _twin

read = _twin.of("sender_host_ms_per_msg.queue")
