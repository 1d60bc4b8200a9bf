"""Model families as data: a configuration file names its family
(``bench/families/<name>.py``) and may cut the program's configuration
(``"program"``, each cut key listed in ``reduced``). A new family and its
configuration, written only under a temporary checkout, run through the
harness unchanged. On the CPU at a tiny size; run by hand:
``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import tiny
import run

JUNK = shutil.ignore_patterns("out", ".jax_cache", "__pycache__", "tests")


@pytest.fixture(autouse=True)
def _reduced(monkeypatch):
    tiny.reduce_archs(monkeypatch)


def _tree_hash() -> str:
    """Every file of the benchmark as it is in the repository."""
    h = hashlib.sha256()
    for p in sorted(tiny.BENCH.rglob("*")):
        rel = p.relative_to(tiny.BENCH.parent)
        if p.is_file() and not {"out", ".jax_cache",
                                "__pycache__"} & set(rel.parts):
            h.update(str(rel).encode() + p.read_bytes())
    return h.hexdigest()


def _checkout(tmp: Path, name: str, cfg: dict,
              families: dict = None) -> Path:
    """A checkout under ``tmp`` holding the benchmark's files, one
    configuration ``name`` with one chat cell, and ``families`` (file name
    -> source) added to ``bench/families/``."""
    shutil.copytree(tiny.BENCH, tmp / "bench", ignore=JUNK)
    for fname, src in (families or {}).items():
        (tmp / "bench" / "families" / fname).write_text(src)
    (tmp / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    bench = json.loads((tiny.BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": name, "source": cfg["source"],
                         "file": f"bench/configs/{name}.json",
                         "reduced": cfg["reduced"], "why": "a test"}]
    bench["workloads"] = [{"name": f"{name}.chat", "config": name,
                           "traffic": "chat", "chips": 1, "why": "a test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def _qwen() -> dict:
    return json.loads((tiny.BENCH / "configs" / "qwen2.5-3b.json")
                      .read_text())


def _run(c: run.Cell) -> dict:
    return run.run_cell(tiny.shrink(c), 2**31 + 11, 3.0, False, tiny.PEAK,
                        log=lambda m: None)


@pytest.mark.parametrize("workload", sorted(tiny.WORKLOADS.values()))
def test_config_without_family_is_dense_gqa(workload):
    c = run.load_cell(workload)
    assert "family" not in json.loads(
        (tiny.BENCH / "configs" / f"{c.cfg['name']}.json").read_text())
    assert Path(c.family.__file__) == tiny.BENCH / "families" / "dense_gqa.py"


def test_a_new_family_is_new_files_only(tmp_path):
    """A copy of ``dense_gqa`` under another name, and a configuration that
    names it, written under a temporary checkout, load and run correct;
    nothing in the repository's benchmark changes."""
    before = _tree_hash()
    src = (tiny.BENCH / "families" / "dense_gqa.py").read_text()
    cfg = dict(_qwen(), name="gqa-copy", family="gqa_copy")
    root = _checkout(tmp_path, "gqa-copy", cfg, {"gqa_copy.py": src})
    c = run.load_cell("gqa-copy.chat", root)
    assert Path(c.family.__file__) == (root / "bench" / "families"
                                       / "gqa_copy.py").resolve()
    assert c.family is not run.load_cell("qwen2.5-3b.chat").family
    res = _run(c)
    assert res["correct"], res["checks"]
    assert res["compiled_in_window"] == 0 and res["metrics"]
    assert _tree_hash() == before


@pytest.mark.parametrize("listed", [True, False])
def test_program_cut_needs_its_key_in_reduced(tmp_path, listed):
    """``"program": {"n_layers": 3}`` runs the program three layers deep,
    not the reduced architecture's two, where ``num_hidden_layers`` is in
    ``reduced``, and is refused, naming the key, where it is not."""
    qwen = _qwen()
    cfg = dict(qwen, name="qwen-cut", program={"n_layers": 3},
               model=dict(qwen["model"], num_hidden_layers=3),
               reduced=["num_hidden_layers"] if listed else [])
    root = _checkout(tmp_path, "qwen-cut", cfg)
    if listed:
        c = run.load_cell("qwen-cut.chat", root)
        res = _run(c)
        assert c.cfg["model"]["num_hidden_layers"] == 3
        assert res["correct"], res["checks"]
        return
    with pytest.raises(SystemExit, match="'num_hidden_layers'"):
        run.load_cell("qwen-cut.chat", root)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen-cut.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=root,
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and out.stdout == ""
    assert "'num_hidden_layers'" in out.stderr


@pytest.mark.parametrize("key,value", [("rope_theta", 5e5),
                                       ("rms_norm_eps", 1e-5),
                                       ("tie_word_embeddings", False),
                                       ("attention_bias", False)])
def test_tiny_cell_holds_unreduced_keys_to_the_program(tmp_path, key,
                                                       value):
    """The tiny cell restates only the keys ``.reduced()`` changes: a file
    whose ``model`` block departs from the program in any other key is
    still refused, naming the key."""
    import serving
    qwen = _qwen()
    assert qwen["model"][key] != value
    cfg = dict(qwen, name="qwen-off", model=dict(qwen["model"],
                                                 **{key: value}))
    c = tiny.shrink(run.load_cell("qwen-off.chat",
                                  _checkout(tmp_path, "qwen-off", cfg)))
    assert c.cfg["model"][key] == value
    with pytest.raises(ValueError, match=key):
        serving.check_model(c.cfg["model"],
                            serving.program_config(c.cfg, c.family),
                            c.family)


def test_kernel_cost_is_per_step_over_the_layers():
    """``dense_gqa.kernel_cost`` is every layer's paged-attention call, and
    None for a program the family does not know, which the roofline
    reader then leaves out."""
    c = run.load_cell("qwen2.5-3b.chat")
    fam, d = c.family, c.family.dims(c.cfg["model"])
    f, b = fam.paged_attention_cost(d, [10, 20])
    assert fam.kernel_cost(d, "paged_attention", [10, 20]) == (36 * f, 36 * b)
    assert fam.kernel_cost(d, "some_other_kernel", [10, 20]) is None


def test_program_cut_reaches_into_nested_groups():
    """A nested group (``moe``) is cut field by field: the rest of it, and
    of the configuration, stays the program's."""
    from repro.configs import get_arch
    import family
    mc = get_arch("deepseek-v2-lite-16b")
    cut = family.replace(mc, {"n_layers": 7, "moe": {"n_routed": 16}})
    assert (cut.n_layers, cut.moe.n_routed) == (7, 16)
    assert cut.moe.top_k == mc.moe.top_k and cut.mla == mc.mla
    assert cut.d_model == mc.d_model and mc.n_layers != 7
