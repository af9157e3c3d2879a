"""planner/pipeline: host milliseconds of ``pow.launch`` and
``pow.harvest`` spans inside the window, per launch the program
counted, in cells where a solve is a stream: a harvest there also
resolves its hits and a launch refills its group's freed slots."""

from benchmarks.layers._spans import grown, span_seconds


def read(window):
    seconds = span_seconds(window, ("pow.launch", "pow.harvest"))
    launches = grown(window, "pow_pipeline_launches_total")
    if seconds is None or not launches:
        return None
    return seconds * 1e3 / launches
