"""The serving driver's CLI (``repro.launch.serve``) run in-process.

``main`` asserts the crash drill's invariants itself: every completion is
still registered after the crash, no acknowledged request is lost, and the
redelivered requests commit.  Each case is one path of the driver at
``--requests 8``: the single registry, the sharded bucket registry, the
pipelined waves and the snapshot-backed hybrid restart.
"""
import sys

import pytest

from repro.launch import serve

CASES = {
    "single": ["--queue", "--crash"],
    "sharded_bucket": ["--shards", "2", "--queue", "--crash",
                       "--backend", "bucket"],
    "pipelined": ["--shards", "2", "--pipeline", "2", "--queue", "--crash"],
    "snapshot": ["--shards", "2", "--snapshot-every", "1", "--snapshot-dir",
                 "{tmp}", "--queue", "--crash"],
}


@pytest.mark.parametrize("case", list(CASES))
def test_serve_crash_drill(case, tmp_path, capsys, monkeypatch):
    # the persistent compile cache is the entry point's setting; a test
    # leaves JAX's configuration as it found it
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    argv = [a.format(tmp=tmp_path) for a in CASES[case]]
    assert serve.main(["--requests", "8", *argv]) == 0
    out = capsys.readouterr().out
    assert "all 8 completions still registered" in out
    assert "8 acked requests redelivered and committed" in out
    assert "psyncs by phase {'ack': 8, 'record': 8, 'commit': 8}" in out
    if case == "snapshot":
        assert "hybrid recovery:" in out
    loaded = {n.split(".")[1] for n in sys.modules if n.startswith("repro.")}
    assert loaded <= {"core", "kernels", "launch", "obs", "store"}, loaded
