"""solver ladder: trials the dispatcher credited to solved objects
(``pow_trials_total``, all backends) per second of the window, in
millions.  Credit is what an object's own search covered, so it
follows the useful work, not the device's."""


def read(window):
    return window.counters.total("pow_trials_total") / window.seconds / 1e6
