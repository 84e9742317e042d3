"""The comparison that decides ``correct``, held to failing where it must.

Small configurations on the CPU (``bench/tests/small.py``); the harness's
look for a chip is skipped and the rest of a run is driven as it is on the
chip.  Run with

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The control at each cell's own size runs on the chip with
``bench/control.py``.
"""

from __future__ import annotations

import json

from bench.tests import small

ROOT = small.ROOT
SEED = 3_000_000_019          # above 2**31, as seeds may be


def _run(capsys, workload, load=None, seconds=2.0):
    from bench import run
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_chip=False, load=load or small.loader())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_runs_are_correct(capsys):
    for cell in ("small.chat", "small.sessions", "small.sip-tune"):
        out = _run(capsys, cell)
        assert out["correct"], out["checks"]
        assert list(out)[-1] == "checks"


def test_control_fails(monkeypatch):
    """The float8 control reads above the limit that sound runs meet."""
    import jax
    from bench import common, run as R, serve, tune
    load = small.loader()
    for cell, cells in (("small.chat", serve), ("small.sip-tune", tune)):
        c, centry, _, _ = R.select(load("BENCHMARK.json"), cell)
        r = common.Run(root=ROOT, cell=c, config=load(centry["file"]),
                       mix=load(f"bench/traffic/{c['traffic']}.json"),
                       seed=SEED, seconds=2.0, trace=False,
                       devices=jax.devices()[:1], clock=common.CompileClock(),
                       t_start=0.0, trace_dir=R.CACHE / "trace" / "test",
                       control=True)
        prog, ctrl = cells.drive(r).checks
        assert prog.ok, prog
        assert not ctrl.ok, ctrl


def test_token_altered_is_not_correct(capsys, monkeypatch):
    """A served token changed where the engine produces it."""
    from repro.serve import engine as E
    emit = E.ContinuousEngine._emit

    def altered(self, slot, req, tok, finished):
        if len(req.tokens) == 2:
            tok = (tok + 1) % small.CONFIG["vocab_size"]
        return emit(self, slot, req, tok, finished)

    monkeypatch.setattr(E.ContinuousEngine, "_emit", altered)
    assert not _run(capsys, "small.chat")["correct"]


def test_answer_altered_is_not_correct(capsys, monkeypatch):
    """The tuned kernel's output changed where it is produced."""
    from repro.core import jit
    call = jit.SipKernel.__call__

    def altered(self, *args):
        out = call(self, *args)
        return out.at[0, 0, -1, 0].add(1.0)

    monkeypatch.setattr(jit.SipKernel, "__call__", altered)
    assert not _run(capsys, "small.sip-tune")["correct"]


def test_no_chip_no_result(capsys):
    """On the CPU the harness exits non-zero and prints no result."""
    from bench import run
    rc = run.main(["--workload", "qwen3-1.7b.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
