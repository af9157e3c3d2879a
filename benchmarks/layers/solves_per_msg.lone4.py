"""send queue: as ``solves_per_msg``: batches ``PowService`` launched
over messages published.  2.0 while a message's ack and the message
itself are each solved alone, which is what hands each the four chips."""

from benchmarks.layers import _twin

read = _twin.of("solves_per_msg")
