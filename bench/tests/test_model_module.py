"""The seam between the serving harness and a model family: a
configuration's model module, found by the path its file names.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench.tests import small
from bench.tests.test_correct import _run

DENSE = "bench/reference/dense_lm.py"
DATA = "bench/tests/data"


def _with(model, **program):
    """The small configuration naming ``model``, its program's overrides
    changed by ``program``."""
    prog = dict(small.CONFIG["program"],
                overrides=dict(small.CONFIG["program"]["overrides"],
                               **program))
    return dict(small.CONFIG, model=model, program=prog)


@pytest.mark.parametrize("cell", ["small.chat", "small.sessions"])
def test_a_family_added_as_files_is_served_correctly(capsys, cell):
    """A second model module, added as a file that no existing file names,
    serves the chat and sessions mixes and is ``correct``."""
    load = small.loader(config=_with(f"{DATA}/alias_lm.py"))
    out = _run(capsys, cell, load=load)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


def test_a_reference_fault_is_not_correct(capsys):
    """The module's reference without its q/k norm: the served tokens
    leave it by more than the limit."""
    load = small.loader(config=_with(f"{DATA}/no_qk_norm_lm.py"))
    out = _run(capsys, "small.chat", load=load)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("model, program, sides", [
    (f"{DATA}/windowed_lm.py", {}, ("window None", "states 1024")),
    (DENSE, {"n_layers": 3}, ("(3, 64,", "(2, 64,")),
])
def test_a_refused_program_stops_setup(model, program, sides):
    """Set-up raises where the module's ``check_program`` refuses the
    program, naming what the program gives and what the file states."""
    from bench import run
    with pytest.raises(ValueError) as e:
        run.main(["--workload", "small.chat", "--seed", "5", "--seconds",
                  "1", "--trace", "0"], require_chip=False,
                 load=small.loader(config=_with(model, **program)))
    for side in sides:
        assert side in str(e.value)


def _qwen3():
    from bench.reference import dense_lm
    with open(small.ROOT / "bench" / "configs" / "qwen3-1.7b.json") as f:
        return dense_lm, dense_lm.Spec.from_config(json.load(f))


def test_kv_read_bytes_by_hand():
    # one page of K and of V: 16 positions x 8 heads x 128 x 2 B = 32,768 B
    # each; 28 layers
    m, s = _qwen3()
    page = 2 * 16 * 8 * 128 * 2 * 28
    assert page == 1_835_008
    assert m.kv_read_bytes(s, 1, 16) == page
    assert m.kv_read_bytes(s, 16, 16) == page
    assert m.kv_read_bytes(s, 17, 16) == 2 * page
    assert m.kv_read_bytes(s, 1000, 16) == 63 * page == 115_605_504


def test_tokens_record_live_pages_per_step():
    """Each decoded token reads its row's live pages at its context; a
    prefill chunk reads its row's pages through the chunk's end."""
    from bench import serve
    m, s = _qwen3()
    page = 1_835_008
    tokens = serve.Tokens(m, s, 16)
    req = SimpleNamespace(uid=1, prompt=[0] * 100, tokens=[7])
    tokens.on_token(req, 7)            # the first token: prefill's
    assert tokens.kv_bytes == {}
    req.tokens.append(8)               # context 101: 7 pages
    tokens.on_token(req, 8)
    other = SimpleNamespace(uid=2, prompt=[0] * 31, tokens=[1, 2])
    tokens.on_token(other, 2)          # context 32: 2 pages
    assert tokens.kv_bytes == {0: 9 * page}
    tokens.step = 1
    task = SimpleNamespace(req=SimpleNamespace(prompt=[0] * 600), pos=256)
    engine = SimpleNamespace(_chunk_tasks=[task],
                             _chunk_step=lambda finished: None,
                             _decode_paged=lambda finished: None)
    assert tokens.watch(engine, 256)
    engine._chunk_step([])             # positions 256..511: 32 pages
    assert tokens.kv_bytes == {0: 9 * page, 1: 32 * page}
    assert tokens.chunk_flops[1] == m.chunk_flops(s, 256, 256, False)


def test_paged_gather_roofline_by_hand():
    from bench import peaks, run
    read = run.reader(small.ROOT, "paged_gather_roofline")
    traced = SimpleNamespace(kernel_s={"bench.decode": 2.0,
                                       "bench.chunk": 0.5})
    w = SimpleNamespace(traced=traced, traced_kv_bytes=819e9 * 0.25,
                        chips=1, peaks=peaks.for_kind("TPU v5 lite"))
    assert read(w) == pytest.approx(10.0, rel=1e-12)   # 0.25 s of 2.5
    w.traced_kv_bytes = 0.0
    assert read(w) is None
    w.traced = None
    assert read(w) is None
