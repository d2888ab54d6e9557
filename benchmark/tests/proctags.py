"""What the process tests look for: live processes that carry a tag of the
TEST's own (`BENCH_TEST_TAG`, not the one the owner sweeps by) in
/proc/<pid>/environ.  A test that looks for none sees some first."""

import time
import uuid

from lib import procs


def new_tag():
    return uuid.uuid4().hex


def alive(tag):
    return procs.tagged_pids(tag, "BENCH_TEST_TAG")


def gone_within(tag, seconds=5.0):
    deadline = time.monotonic() + seconds
    while alive(tag) and time.monotonic() < deadline:
        time.sleep(0.1)
    return not alive(tag)
