"""Architecture families (``bench/families/<model_type>.py``): what the
benchmark reads of the Qwen configurations is what it read before the
families were split out of ``bench/model.py`` and ``bench/reference.py``
(``data/families_golden.json``, recorded from that code), and a new
architecture is a file of its own, found by its ``model_type``."""

import json
import re
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import model, reference, spec

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
GOLDEN = json.loads((HERE / "data" / "families_golden.json").read_text())
SEED = 2**33 + 5
Q_LENS = np.array([16, 1, 0, 5, 1, 64], np.int32)
CACHE_POS = np.array([0, 150, 3, 40, 2047, 128], np.int32)
TINY = ["tiny-qwen3", "tiny-moe"]


def conf_of(name):
    path = REPO / "bench" / "configs" / f"{name}.json"
    if not path.exists():
        path = HERE / "data" / f"{name}.json"
    return json.loads(path.read_text())


def made(conf):
    held = model.experts_held(conf, model.program_config(conf))
    dev = jax.devices()[0]
    return held, model.make_params(conf, held, SEED, lambda p: dev)


@pytest.mark.parametrize("name", TINY)
def test_seeded_weights_as_before(name):
    """Same leaf paths, shapes, dtypes and flatten order, so the same
    ``fold_in(key, i)`` per leaf: its first values and mean magnitude as
    recorded (to within a bf16 rounding step, so that a CPU that rounds one
    value the other way still passes; a leaf made from another index reads
    nothing alike)."""
    conf, want = conf_of(name), GOLDEN[name]
    held, params = made(conf)
    assert held == want["held"]
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [[jax.tree_util.keystr(p), list(a.shape), str(a.dtype)] for p, a in flat] == \
        [w[:3] for w in want["leaves"]]
    for (_, a), w in zip(flat, want["leaves"]):
        f = np.asarray(a).astype(np.float64)
        np.testing.assert_allclose(f.ravel()[:4], w[3], rtol=2**-7, atol=1e-6)
        assert np.abs(f).mean() == pytest.approx(w[4], rel=1e-4)


@pytest.mark.parametrize("name", TINY + ["qwen3-14b-10L", "qwen2-moe-a2.7b-6L"])
def test_work_counts_as_before(name):
    """The counts behind ``mfu`` and ``flash_roofline``, exactly."""
    conf, want = conf_of(name), GOLDEN[name]
    held = model.experts_held(conf, model.program_config(conf))
    assert held == want["held"]
    assert model.param_count(conf, held) == want["param_count"]
    assert model.active_matmul_params(conf) == want["active_matmul_params"]
    assert model.kv_bytes_per_token(conf) == want["kv_bytes_per_token"]
    assert model.step_flops(conf, Q_LENS, CACHE_POS) == want["step_flops"]
    assert list(model.attention_call(conf, Q_LENS, CACHE_POS)) == want["attention_call"]
    assert model.describe(conf, held) == want["describe"]


@pytest.mark.parametrize("name", TINY)
def test_reference_gaps_as_before(name):
    """The reference's readings, served and float8 control, on the recorded
    pairs."""
    conf, want = conf_of(name), GOLDEN[name]["gaps"]
    _, params = made(conf)
    rng = np.random.default_rng(7)
    seqs = [(rng.integers(0, conf["vocab_size"], pl).tolist(),
             rng.integers(0, conf["vocab_size"], ol).tolist())
            for pl, ol in ((40, 9), (150, 30), (7, 3))]
    got = reference.gaps(params, conf, seqs, pad_to=512, out_max=32, control=True)
    for k in ("served", "control"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", TINY)
def test_each_family_is_found_by_its_model_type(name):
    conf = conf_of(name)
    fam = model.family(conf)
    assert Path(fam.__file__).name == f"{conf['model_type']}.py"
    assert all(callable(getattr(fam, f)) for f in spec.FAMILY_API)


TOY = '''"""A toy architecture: each layer a pre-norm GELU MLP, no attention; the
head tied to the embedding."""

import jax

from bench.model import Leaf
from bench.reference import linear, rmsnorm


def program_config(conf):
    raise NotImplementedError("the program serves no toy")


def experts_held(conf, cfg):
    return 0


def head_dim(conf):
    return 0


def layout(conf, held):
    d, f, v = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    mat = lambda *s: Leaf(s, conf["torch_dtype"], "matrix")
    norm = Leaf((d,), "float32", "norm")
    return {"embed": mat(v, d), "ln_final": norm,
            "layers": [{"ln": norm, "w_in": mat(d, f), "w_out": mat(f, d)}
                       for _ in range(conf["num_hidden_layers"])]}


def active_matmul_params(conf):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    return conf["num_hidden_layers"] * 2 * d * f + d * conf["vocab_size"]


def step_flops(conf, q_lens, cache_pos):
    return 2.0 * active_matmul_params(conf) * float(q_lens.sum())


def attention_call(conf, q_lens, cache_pos, dtype_bytes=2):
    return 0.0, 0.0


def kv_bytes_per_token(conf):
    return 0


def layer(p, x, conf, quant):
    h = rmsnorm(x, p["ln"], conf["rms_norm_eps"])
    return x + linear(jax.nn.gelu(linear(h, p["w_in"], quant)), p["w_out"], quant)
'''


@pytest.fixture
def toy_root(tiny_root):
    """The tiny checkout plus a family, a configuration and a cell of a
    model type that no harness file knows."""
    bench = tiny_root / "bench"
    (bench / "families" / "toy_mlp_test.py").write_text(TOY)
    conf = {"model_type": "toy_mlp_test", "hidden_size": 64, "intermediate_size": 96,
            "num_hidden_layers": 3, "vocab_size": 512, "rms_norm_eps": 1e-6,
            "torch_dtype": "bfloat16", "initializer_range": 0.05,
            "serving": {"chips": 1}}
    (bench / "configs" / "toy.json").write_text(json.dumps(conf))
    bj = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bj["workloads"].append({"name": "toy.tiny-open", "config": "toy",
                            "traffic": "tiny-open", "chips": 1, "why": "t"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bj))
    return tiny_root


def test_a_new_family_is_files_only(toy_root):
    """Loaded by ``model_type`` from the checkout's own files; the shared
    weights, counts and reference then run on it."""
    cell = spec.load_cell(toy_root, "toy.tiny-open")
    conf = cell.config
    fam = model.family(conf)
    assert Path(fam.__file__) == toy_root / "bench" / "families" / "toy_mlp_test.py"
    assert model.param_count(conf, 0) == 512 * 64 + 64 + 3 * (64 + 2 * 64 * 96)
    assert model.step_flops(conf, Q_LENS, CACHE_POS) == \
        2.0 * (3 * 2 * 64 * 96 + 64 * 512) * int(Q_LENS.sum())
    assert "KV 0 KiB/token" in model.describe(conf, 0)
    dev = jax.devices()[0]
    params = model.make_params(conf, 0, 11, lambda p: dev)
    assert params["layers"][2]["w_in"].shape == (64, 96)

    # greedy tokens of the reference sit at gap 0; any others above it
    prompt = [5, 17, 400, 3]
    served = []
    for _ in range(3):
        toks = np.zeros(512, np.int32)
        full = prompt + served
        toks[: len(full)] = full
        h = reference.hidden(params, conf, toks, False)[len(full) - 1:len(full)]
        logits = reference._logits(params["ln_final"], params["embed"].T, h,
                                   conf["rms_norm_eps"], False)
        served.append(int(np.argmax(logits[0])))
    g = reference.gaps(params, conf, [(prompt, served)], pad_to=512, out_max=8)
    assert g["served"] == [0.0, 0.0, 0.0]
    wrong = [(t + 1) % 512 for t in served]
    g = reference.gaps(params, conf, [(prompt, wrong)], pad_to=512, out_max=8)
    assert min(g["served"]) > 0.0


def test_a_missing_family_fails_naming_its_file(tiny_root):
    conf = json.loads((tiny_root / "bench" / "configs" / "tiny-qwen3.json").read_text())
    conf["model_type"] = "no_such_family"
    (tiny_root / "bench" / "configs" / "tiny-qwen3.json").write_text(json.dumps(conf))
    want = tiny_root / "bench" / "families" / "no_such_family.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(want))):
        spec.load_cell(tiny_root, "tiny-qwen3.tiny-open")


def test_a_family_short_of_a_function_is_an_error(tiny_root):
    fam = tiny_root / "bench" / "families"
    shutil.copy(fam / "qwen3.py", fam / "qwen3_short.py")
    (fam / "qwen3_short.py").write_text(
        (fam / "qwen3.py").read_text().replace("def kv_bytes_per_token(", "def _gone("))
    with pytest.raises(ValueError, match="kv_bytes_per_token"):
        spec.load_family(tiny_root / "bench", "qwen3_short")
