"""planner/pipeline: of the launches the pipeline dispatched in the
window (``pow_pipeline_device_launches_total``, by the device of the
launch group), the share of the chip that took most.  25 is even on
four chips; 100 is one chip doing all of it."""


def read(window):
    by_device = window.counters.delta("pow_pipeline_device_launches_total")
    total = sum(by_device.values())
    if not total:
        return None
    return 100.0 * max(by_device.values()) / total
