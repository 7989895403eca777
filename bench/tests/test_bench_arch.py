"""The architecture seam (``bench/arch/``): each configuration is served by
the module named by its ``model_type``, which gives the engine's
``ModelConfig``, the work counts, the weights and the reference that the
benchmark had before the seam (pinned in ``data/arch_pins.json``), and a
new ``model_type`` is served by adding a module file and nothing else."""

import copy
import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import arch  # noqa: E402
import harness  # noqa: E402
from test_bench_cells import (assert_line, tiny_config,  # noqa: E402
                              tiny_traffic)

PINS = json.loads((Path(__file__).parent / "data" / "arch_pins.json")
                  .read_text())
CONFIGS = sorted(PINS)
PIN_SEED = 2**31 + 7


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_engine_config_is_the_parents(name):
    cfg = _config(name)
    got = arch.for_model(cfg["model"]).engine_config(cfg)
    assert dataclasses.asdict(got) == PINS[name]["engine_config"]


@pytest.mark.parametrize("name", CONFIGS)
def test_work_counts_are_the_parents(name):
    m = _config(name)["model"]
    s = arch.for_model(m).shape(m)
    want = PINS[name]["flops"]
    assert (s.layers, s.attn_layers, s.vocab) == (
        want["layers"], want["layers"], want["vocab"])
    for ctx, v in want["decode_flops"].items():
        assert s.decode_flops(int(ctx)) == v
    for ctx, v in want["decode_attn_cost"].items():
        assert list(s.decode_attn_cost(int(ctx))) == v
    for key, v in want["prefill_flops"].items():
        assert s.prefill_flops(*map(int, key.split(","))) == v
    for key, v in want["prefill_attn_cost"].items():
        assert list(s.prefill_attn_cost(*map(int, key.split(",")))) == v


def _tiny(name):
    m = _config(name)["model"]
    model = arch.for_model(m)
    tm = model.tiny(m)
    s = model.shape(tm)
    return model, tm, s, model.draw(s, PIN_SEED, jnp.bfloat16)


@pytest.mark.parametrize("name", CONFIGS)
def test_tiny_weights_are_the_parents(name):
    _, _, _, w = _tiny(name)
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PINS[name]["tiny_weights_sha256"]


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "fp8"])
@pytest.mark.parametrize("name", CONFIGS)
def test_tiny_reference_logits_are_the_parents(name, quant):
    model, tm, _, w = _tiny(name)
    want = PINS[name]["tiny_logits" + ("_fp8" if quant else "")]
    a = model.reference(tm)
    x = model.forward(a, w, want["tokens"], want["first"], quant=quant)
    lg = np.asarray(model.logits(a, w, x, want["first"], quant=quant))[:16]
    np.testing.assert_allclose(lg[:, :16], want["rows"], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(lg.max(-1), want["row_max"], rtol=1e-5,
                               atol=1e-6)
    assert lg.argmax(-1).tolist() == want["row_argmax"]
    assert lg.astype(np.float64).sum() == pytest.approx(want["sum"],
                                                        rel=1e-5, abs=1e-4)


NEW_MODULE = '''"""A stand-in architecture: the Llama block under another name."""
from arch.llama import *  # noqa: F401,F403
'''


@pytest.fixture
def arch_dir(tmp_path, monkeypatch):
    """A second directory of architecture modules; whatever is imported
    from it is forgotten afterwards."""
    monkeypatch.setattr(arch, "__path__", [*arch.__path__, str(tmp_path)])
    before = set(sys.modules)
    yield tmp_path
    for name in set(sys.modules) - before:
        if name.startswith("arch."):
            del sys.modules[name]
            if hasattr(arch, name[5:]):
                delattr(arch, name[5:])


def test_a_new_model_type_is_served_by_a_new_file(arch_dir):
    """A module file named for a new ``model_type`` serves it:
    ``run_cell`` runs a configuration of that type with nothing else
    changed."""
    (arch_dir / "stand_in_block.py").write_text(NEW_MODULE)
    name = harness.load_cell("granite8b-chat").name

    def stand_in(c):
        c = copy.deepcopy(c)
        c["model"]["model_type"] = "stand_in_block"
        return tiny_config(c)

    found = arch.for_model({"model_type": "stand_in_block"})
    assert Path(found.__file__) == arch_dir / "stand_in_block.py"
    lines = []
    out = harness.run_cell(name, 2**31 + 5, 0.6, False,
                           config_override=stand_in,
                           traffic_override=tiny_traffic,
                           peak={"bf16_flops_per_s": 1e12,
                                 "hbm_bytes_per_s": 1e11},
                           log=lines.append)
    line = assert_line(out, lines, harness.load_cell(name))
    assert line["correct"], lines
    assert line["checks"]["tokens_compared"]["value"] >= 8


@pytest.mark.parametrize("mtype", ["no_such_block", "no-such.block", None])
def test_an_unknown_model_type_names_the_file_to_add(mtype):
    with pytest.raises(LookupError, match=r"add .*/arch/"):
        arch.for_model({} if mtype is None else {"model_type": mtype})


def test_a_module_whose_own_import_fails_is_not_called_missing(arch_dir):
    (arch_dir / "broken_block.py").write_text(
        "import no_such_package_anywhere  # noqa: F401\n")
    with pytest.raises(ModuleNotFoundError, match="no_such_package"):
        arch.for_model({"model_type": "broken_block"})


def test_granitemoe_is_the_llama_block():
    import arch.llama as llama
    moe = arch.for_model({"model_type": "granitemoe"})
    for fn in llama.__all__:
        assert getattr(moe, fn) is getattr(llama, fn)
