#!/usr/bin/env python3
"""Records the small chip trace that ``test_tracing.py`` reads.

    python3 chipbench/tests/capture_trace.py <out_dir>

One traced ``paper-steady`` run with a short window; the ``.xplane.pb`` it
writes is copied to the directory given, with the reduction's numbers
beside it (``expected.json``), for ``chipbench/tests/data/``.
"""
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import run  # noqa: E402


def main(out_dir: str) -> None:
    run._paths()
    from chipbench import tracing
    run.simulate("paper-steady", 5, 0.05, traced=True)
    src = sorted(glob.glob(os.path.join(run.TRACE_DIR, "plugins", "profile",
                                        "*", "*.xplane.pb")))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(src, os.path.join(out_dir, "paper_steady.xplane.pb"))
    red = tracing.reduce_events(tracing.load(run.TRACE_DIR), run.KERNEL)
    with open(os.path.join(out_dir, "expected.json"), "w") as f:
        json.dump(red, f, indent=1)
    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    print(json.dumps(red))


if __name__ == "__main__":
    main(sys.argv[1])
