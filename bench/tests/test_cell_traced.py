"""A traced run end to end on the CPU: the per-layer readers, an added one
among them, report what they can read; the device's own stay out, since the
CPU has no device trace, and each one left out is named."""
from bench.tests.test_cell import _result, root  # noqa: F401


def test_exact_cell_traced_with_an_added_reader(root):
    line, err = _result(root, "--workload", "tiny-exact.mixed", "--seed", "2147483999",
                        "--trace", "1")
    got = set(line["metrics"])
    # no device trace on the CPU: the device readers find nothing and stay out
    assert got == {"batch_exec_ms", "filler_share", "prefilter_share", "steps_per_query",
                   "dist_per_query", "build_s", "batches_traced"}
    assert "per-layer metric device_idle_share read nothing" in err
    assert line["device"]["busy_s"] is None and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["metrics"]["filler_share"]["value"] == 0.0
    # one micro-batch of each of the mix's two shapes was traced
    assert line["metrics"]["batches_traced"]["value"] == 2
