"""sender: as ``sender_host_ms_per_msg.queue`` (``sender.sign``,
``sender.encrypt`` and ``sender.publish`` spans inside the window, per
message published), where sends end four times as often and their
crypto shares the interpreter lock with the launches of every chip."""

from benchmarks.layers import _twin

read = _twin.of("sender_host_ms_per_msg.queue")
