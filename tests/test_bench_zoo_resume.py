"""bench_zoo --resume retention invariants.

The zoo sweep's tracked JSON holds hour-scale real-chip records; the
resume/preserve/supersede logic guards them across filtered passes,
mid-sweep aborts, and mixed feed-staging sweeps (reference discipline:
benchmark/README.md published-numbers contract). These tests stub the
per-config subprocess so the invariants run in-suite without a chip.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

import bench_zoo


def _run(monkeypatch, tmp_path, argv, fail=()):
    """Drive bench_zoo.main with a stubbed per-config runner."""
    out = tmp_path / "zoo.json"
    ran = []

    def fake_run_config(name, extra, batch, iterations, force_cpu):
        ran.append(name)
        if name in fail:
            return {"config": name, "error": "boom", "wall_sec": 0.1}
        rec = {"config": name, "model": name.split("_")[0],
               "batch_size": batch, "examples_per_sec": 100.0,
               "wall_sec": 0.1}
        if "--staged_feed" in extra:
            rec["staged_feed"] = int(
                extra[extra.index("--staged_feed") + 1])
            rec["staged_transfer"] = True
        return rec

    monkeypatch.setattr(bench_zoo, "run_config", fake_run_config)
    monkeypatch.setattr(sys, "argv",
                        ["bench_zoo.py", "--out", str(out)] + argv)
    try:
        bench_zoo.main()
        code = 0
    except SystemExit as e:
        code = e.code or 0
    with open(out) as f:
        data = json.load(f)
    return data, ran, code


def _rows(data):
    return sorted((r["config"], r.get("staged_feed", 0),
                   bool(r.get("error"))) for r in data["configs"])


def test_only_filter_preserves_unreached_records(monkeypatch, tmp_path):
    data, ran, _ = _run(monkeypatch, tmp_path,
                        ["--only", "mnist_cnn,vgg16_cifar10"])
    assert len(data["configs"]) == 2
    # a second, filtered pass must not delete the other completed row
    data, ran, _ = _run(monkeypatch, tmp_path,
                        ["--only", "mnist_cnn", "--resume"])
    assert ran == []          # same staging: kept, not re-run
    assert len(data["configs"]) == 2


def test_staged_resume_remeasures_but_keeps_hostfeed_rows(
        monkeypatch, tmp_path):
    data, _, _ = _run(monkeypatch, tmp_path, ["--only", "mnist_cnn"])
    assert _rows(data) == [("mnist_cnn", 0, False)]
    # staged resume: host-feed row is NOT a match (re-measure) and NOT
    # discarded (different measurement, kept alongside)
    data, ran, _ = _run(monkeypatch, tmp_path,
                        ["--only", "mnist_cnn", "--resume",
                         "--staged", "4"])
    assert ran == ["mnist_cnn"]
    assert _rows(data) == [("mnist_cnn", 0, False),
                           ("mnist_cnn", 4, False)]
    # resuming the staged sweep again: both rows survive, nothing re-runs
    data, ran, _ = _run(monkeypatch, tmp_path,
                        ["--only", "mnist_cnn", "--resume",
                         "--staged", "4"])
    assert ran == []
    assert _rows(data) == [("mnist_cnn", 0, False),
                           ("mnist_cnn", 4, False)]


def test_failed_rerun_supersedes_nothing(monkeypatch, tmp_path):
    data, _, _ = _run(monkeypatch, tmp_path, ["--only", "mnist_cnn"])
    # the re-measure fails: the completed row must survive next to the
    # error row, and the sweep must exit nonzero
    data, _, code = _run(monkeypatch, tmp_path,
                         ["--only", "mnist_cnn", "--resume",
                          "--staged", "4"],
                         fail={"mnist_cnn"})
    assert code == 5
    assert _rows(data) == [("mnist_cnn", 0, False),
                           ("mnist_cnn", 0, True)]


def test_fresh_rerun_supersedes_same_staging_row(monkeypatch, tmp_path):
    data, _, _ = _run(monkeypatch, tmp_path, ["--only", "mnist_cnn"])
    # same-staging re-measure WITHOUT --resume: one row, not two
    data, ran, _ = _run(monkeypatch, tmp_path, ["--only", "mnist_cnn"])
    assert ran == ["mnist_cnn"]
    assert _rows(data) == [("mnist_cnn", 0, False)]
