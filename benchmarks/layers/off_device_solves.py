"""solver ladder: solves of the window that ran on a backend the
configuration does not name (``pow_attempts_total`` by backend).
Must be 0: a solve that fell to C++ gives a right nonce and a time
that is not the chip's."""


def read(window):
    return window.verdict["off_device_solves"]
