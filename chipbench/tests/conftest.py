"""Self-checks of the benchmark, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "chipbench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: a dense block at tiny widths, with the keys of a real configuration file
TINY = {
    "source": "https://huggingface.co/microsoft/Phi-4-mini-instruct",
    "program_arch": "phi4-mini-3.8b", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 3, "vocab_size": 256, "tie_word_embeddings": True,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "mesh": [1, 1],
}


@pytest.fixture(autouse=True)
def off_chip(monkeypatch):
    """The tests run on the CPU: the harness's look for a TPU is skipped
    and the readers take the v5e's peaks.  Returns the real look."""
    import jax

    import harness

    real_chips, real_peak = harness.chips, harness.peak_of
    monkeypatch.setattr(harness, "chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peak_of", lambda kind: real_peak("TPU v5 lite"))
    return real_chips


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like directory with one tiny cell per plan kind; the
    benchmark's code is the repository's, the data files are these."""

    def make(limits=None, untied=False):
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        (tmp_path / "chipbench" / "configs").mkdir(parents=True, exist_ok=True)
        (tmp_path / "chipbench" / "traffic").mkdir(exist_ok=True)
        (tmp_path / "chipbench" / "limits").mkdir(exist_ok=True)
        m = dict(TINY, tie_word_embeddings=not untied)
        (tmp_path / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(m))
        base = json.loads((ROOT / "chipbench" / "traffic" / "b2s2048.json").read_text())
        for plan in ("time_centric", "sqrtn"):
            t = dict(base, seq_len=128, plan=plan, trace_steps=2)
            (tmp_path / "chipbench" / "traffic" / f"tiny.{plan}.json").write_text(json.dumps(t))
        bench["configs"] = [dict(bench["configs"][0], name="tiny",
                                 file="chipbench/configs/tiny.json")]
        bench["workloads"] = [
            {"name": f"tiny.{p}", "config": "tiny", "traffic": f"tiny.{p}", "chips": 1,
             "why": "tiny"} for p in ("time_centric", "sqrtn")]
        for x in bench["per_layer"]:
            x["workloads"] = [w["name"] for w in bench["workloads"]]
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        if limits is not None:
            for w in bench["workloads"]:
                (tmp_path / "chipbench" / "limits" / f"{w['name']}.json").write_text(
                    json.dumps(limits))
        return tmp_path

    return make
