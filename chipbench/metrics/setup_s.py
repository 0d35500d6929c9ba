"""Seconds from process start to the window's opening: imports, device,
trace, warm-up compiles and the set-up rounds."""

def read(rec):
    return rec["setup_s"]
