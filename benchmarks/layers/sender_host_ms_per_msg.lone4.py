"""sender: as ``sender_host_ms_per_msg`` (``sender.sign``,
``sender.encrypt`` and ``sender.publish`` spans inside the window, per
message published), where the chips wait for it between two solves."""

from benchmarks.layers import _twin

read = _twin.of("sender_host_ms_per_msg")
