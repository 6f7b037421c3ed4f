"""The comparison fails where it must: the control (the reference at one
precision step below the configuration's) and each fault the one-chip
serving cells can have, planted under the timed path."""
import json

import numpy as np
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write_root(tmp_path_factory.mktemp("bench-root"))


def _checks(root, *extra):
    code, out, err = tiny.main(root, "--workload", "tiny-exact.mixed", "--seed", "11",
                               "--seconds", "1", "--trace", "0", "--cpu-rehearsal", *extra)
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    return line["correct"], line["checks"]


def _patch_dispatch(monkeypatch, alter):
    from repro.serving.tenancy.service import CollectionService

    real = CollectionService._dispatch

    def broken(self, col, t_bucket, full):
        return alter(real(self, col, t_bucket, full))

    monkeypatch.setattr(CollectionService, "_dispatch", broken)


def test_control_fails(root):
    correct, checks = _checks(root, "--control")
    assert not correct
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]


def test_half_the_batch_left_out(monkeypatch, root):
    _patch_dispatch(monkeypatch, lambda results: results[: len(results) // 2])
    correct, checks = _checks(root)
    assert not correct
    assert checks["lost"]["value"] > 0


def test_answer_altered_where_produced(monkeypatch, root):
    def alter(results):
        for r in results:
            r.ids = np.where(r.ids >= 0, (r.ids + 1) % 1000, r.ids).astype(r.ids.dtype)
        return results

    _patch_dispatch(monkeypatch, alter)
    correct, checks = _checks(root)
    assert not correct
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]
