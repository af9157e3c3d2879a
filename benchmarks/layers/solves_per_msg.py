"""send queue: batches ``PowService`` launched (``pow_batch_size``
observations grown in the window) over messages published.  2.0 while
a message's ack and the message itself are each solved alone; nearer 1
if they ever share a batch."""


def read(window):
    _objects, batches = window.counters.hist("pow_batch_size")
    published = len(window.published)
    if not batches or not published:
        return None
    return batches / published
