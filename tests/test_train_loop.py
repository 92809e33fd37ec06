"""``launch/train.py``'s loop on the profiler clock, at smoke size on the CPU.

One run of ``main`` with two steps traced and the batch's shape changed
for one step late in the run: the traced steps hold the loop's host
spans in order, the log line keeps its form, and the one recompile is
counted and reported on the straggler line and the last line.
"""
import contextlib
import glob
import io
import re

import pytest

from repro.launch import trace_names as tn
from repro.launch import train

STEPS, RESHAPED = 14, 12


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    prof = tmp_path_factory.mktemp("prof")
    make = train.make_batch_fn

    def make_batch_fn(*a, **kw):
        fn = make(*a, **kw)

        def batch(step):
            b = fn(step)
            if step == RESHAPED:          # one step one position shorter
                b = {k: v[:, :-1] for k, v in b.items()}
            return b
        return batch

    log = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, \
            contextlib.redirect_stdout(log):
        mp.setattr(train, "make_batch_fn", make_batch_fn)
        rc = train.main(["--arch", "zaremba-medium", "--smoke",
                         "--steps", str(STEPS), "--batch", "2",
                         "--seq", "8", "--log-every", "1",
                         "--profile-dir", str(prof),
                         "--profile-steps", "1:2"])
    assert rc == 0
    return log.getvalue(), prof


def _host_events(prof):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{prof}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == tn.STEP or e.name.startswith(tn.STEP + ".")]


def test_traced_steps_hold_the_spans_in_order(run):
    events = _host_events(run[1])
    steps = [e for e in events if e[0] == tn.STEP]
    assert sorted(int(e[3]["step_num"]) for e in steps) == [1, 2]
    spans = [e for e in events if e[0] != tn.STEP]
    assert len(spans) == len(tn.SPANS) * len(steps)
    for _, lo, hi, _ in steps:
        inside = sorted((s, n) for n, s, e, _ in spans if lo <= s < e <= hi)
        assert tuple(n for _, n in inside) == tn.SPANS


def test_log_line_keeps_its_form(run):
    # the form chip_smoke.py's "published" phase reads
    rows = re.findall(r"^step\s+(\d+)\s+loss\s+(\S+)\s+(\d+) ms$", run[0],
                      re.M)
    assert [int(s) for s, _, _ in rows] == list(range(STEPS))
    assert all(float(loss) > 0 for _, loss, _ in rows)


def test_layout_line_is_printed_once(run):
    # on the CPU every default layout is row-major: nothing is pinned
    lines = re.findall(r"^\[layout\] .*$", run[0], re.M)
    assert lines == ["[layout] 0 gradient leaves pinned to their "
                     "parameters' layouts (0.0 MB)"]


def test_a_new_batch_shape_counts_one_compile(run):
    out = run[0]
    assert re.search(rf"^\[straggler\] step {RESHAPED} took .*; 1 compiles,",
                     out, re.M)
    last = out.strip().splitlines()[-1]
    assert last.startswith(f"done: {STEPS} steps")
    assert re.search(r", 1 compiles after the first step \(\d", last)
