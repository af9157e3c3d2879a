"""sender: host milliseconds of the sender's admission passes
(``sender_admit_seconds``: the read of the queued rows of the sent
table and the filter against the sends in flight, once a ``_sweep``
that reads the table) grown between the window's two snapshots, per
broadcast the window published.  The second snapshot lies after the
last sweep returned (in a traced run, after the device's launches were
read), so a pass made in between is counted and the broadcasts it
admitted are not: at most one pass of some 330 in a window.  None
where no pass was observed: a program that has no such series."""


def read(window):
    seconds, passes = window.counters.hist("sender_admit_seconds")
    published = len(window.published)
    if not passes or not published:
        return None
    return seconds * 1e3 / published
