"""Seconds from the start of the process to the start of the measured
window: imports, building the deployment, the recipient's key, and the
warm-up sweeps with everything they trace, lower, compile or load."""


def read(window):
    return window.setup_s
