"""A model family comes in as files found by name: the configuration
file's mapping to the program's ModelConfig, the seeded weights, and the
reference that the check and the control call, on the CPU."""

import copy
import dataclasses
import hashlib
import sys
import time
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import check, control, harness, weights  # noqa: E402
from repro import configs  # noqa: E402
from repro.models.common import ModelConfig  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 33 + 77


@pytest.fixture(scope="module", autouse=True)
def caches():
    harness.configure_caches()


def config_file(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def moe_mla():
    return harness.load_json(DATA / "tiny-moe-mla.json")


# every field that differs from ModelConfig's defaults, as the two
# configuration files gave them before families came in as files
PINNED = {
    "smollm-135m": dict(
        name="smollm-135m", n_layers=30, d_model=576, n_heads=9,
        n_kv_heads=3, d_ff=1536, vocab=49152, max_seq=2048, norm_eps=1e-05,
        tie_embeddings=True, dtype=jnp.bfloat16),
    "qwen2-7b": dict(
        name="qwen2-7b", n_layers=8, d_model=3584, n_heads=28, n_kv_heads=4,
        d_head=128, d_ff=18944, vocab=152064, max_seq=131072,
        rope_theta=1000000.0, qkv_bias=True, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_existing_configs_map_as_before(name):
    mc = harness.model_config(config_file(name))
    default = ModelConfig()
    for f in dataclasses.fields(ModelConfig):
        want = PINNED[name].get(f.name, getattr(default, f.name))
        assert getattr(mc, f.name) == want, f.name


@pytest.mark.parametrize("name,bias", [("qwen2-7b", True),
                                       ("smollm-135m", False)])
def test_reference_reads_the_bias_as_published(name, bias):
    # Qwen2's config.json has no attention_bias; its architecture has
    # Q, K and V biases, which the program block states for the program
    cfg = config_file(name)
    dims = dict(harness.reference_of(cfg).model_dims(cfg["model"]))
    assert dims["qkv_bias"] is bias
    assert harness.model_config(cfg).qkv_bias is bias


def _tiny_tree():
    s = jax.ShapeDtypeStruct
    bf, f32 = jnp.bfloat16, jnp.float32
    return {"embed": s((64, 16), bf), "head": s((16, 64), bf),
            "ln_f": s((16,), bf),
            "stage0": {"sub0": {
                "ln1": s((2, 16), bf), "ln2": s((2, 16), f32),
                "mixer": {"wq": s((2, 16, 32), bf), "bq": s((2, 32), bf),
                          "wo": s((2, 32, 16), f32)},
                "mlp": {"w_gate": s((2, 16, 24), bf),
                        "w_down": s((2, 24, 16), bf)}}}}


def test_weights_unchanged_for_a_seed():
    # sha256 of every leaf's bytes in tree order, as the weights were
    # drawn before gains were found by name
    h = hashlib.sha256()
    for a in jax.tree.leaves(weights.make(_tiny_tree(), SEED)):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == ("932f7707178d2abbff46cc8041b245953bf3168123f5"
                             "fe55a67716759d6f9b47")


@pytest.mark.parametrize("name", ["q_norm", "k_norm", "kv_norm", "ssm_norm",
                                  "ln1", "ln_f"])
def test_norm_gains_drawn_as_gains(name):
    like = {"mixer": {name: jax.ShapeDtypeStruct((4, 512), jnp.float32),
                      "wq": jax.ShapeDtypeStruct((4, 512, 8), jnp.float32)}}
    w = weights.make(like, SEED)["mixer"]
    g = np.asarray(w[name])
    assert abs(g.mean() - 1.0) < 0.02 and 0.08 < g.std() < 0.12
    # a matrix beside it keeps its scale: normal over sqrt(fan-in)
    assert abs(np.asarray(w["wq"]).std() - 512 ** -0.5) < 0.005


def test_moe_mla_file_maps_to_the_fields_it_states():
    cfg = moe_mla()
    mc = harness.model_config(cfg)
    m = cfg["model"]
    assert (mc.family, mc.use_mla, mc.q_lora_rank, mc.capacity_factor) == (
        "moe", True, 0, 8.0)
    assert (mc.n_experts, mc.top_k, mc.moe_d_ff, mc.n_shared_experts,
            mc.n_dense_layers) == (m["n_routed_experts"],
                                   m["num_experts_per_tok"],
                                   m["moe_intermediate_size"],
                                   m["n_shared_experts"],
                                   m["first_k_dense_replace"])
    assert (mc.kv_lora_rank, mc.qk_nope_dim, mc.qk_rope_dim,
            mc.v_head_dim) == (m["kv_lora_rank"], m["qk_nope_head_dim"],
                               m["qk_rope_head_dim"], m["v_head_dim"])
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv_heads, mc.d_head,
            mc.d_ff, mc.vocab, mc.tie_embeddings, mc.dtype) == (
        3, 128, 4, 4, 32, 256, 512, False, jnp.float32)
    # where the file states a size, none of the registry entry's is left:
    # deepseek-v3-671b has 256 experts, top-8, a 1536-rank q-LoRA
    base = configs.get_config(cfg["arch"])
    assert (base.n_experts, base.top_k, base.q_lora_rank) == (256, 8, 1536)


def _unknown_field(c):
    c["program"]["n_expert"] = 8


def _stray_key(c):
    c["model"]["sliding_window"] = 4096


def _field_set_twice(c):
    c["program"]["d_ff"] = 512


def _mapped_key_missing(c):
    del c["model"]["attention_bias"]


def _not_mapped_but_used(c):
    c["not_mapped"]["hidden_size"] = "said unused, but mapped"


def _not_mapped_not_published(c):
    c["not_mapped"]["sliding_window"] = "listed, but not published"


@pytest.mark.parametrize("spoil", [
    _unknown_field, _stray_key, _field_set_twice, _mapped_key_missing,
    _not_mapped_but_used, _not_mapped_not_published])
def test_unaccounted_key_or_field_stops_before_any_compile(spoil):
    cfg = copy.deepcopy(moe_mla())
    spoil(cfg)
    compiles = harness._Compiles()
    compiles.on = True
    with pytest.raises(ValueError):
        harness.build_engine(cfg, STANDIN_MIX, SEED)
    compiles.on = False
    assert compiles.n == 0


def test_missing_mapped_key_is_named():
    cfg = copy.deepcopy(moe_mla())
    del cfg["model"]["attention_bias"]
    with pytest.raises(ValueError, match="'attention_bias'.*'qkv_bias'"):
        harness.model_config(cfg)


@pytest.mark.parametrize("key,field,value,want", [
    ("attention_bias", "qkv_bias", True, True),
    ("torch_dtype", "dtype", "bfloat16", jnp.bfloat16)])
def test_unpublished_mapped_key_comes_from_the_program_block(
        key, field, value, want):
    # Mixtral publishes no attention_bias; newer files say dtype, not
    # torch_dtype: the program block gives the field, and a published
    # key the mapping does not know is listed under not_mapped
    cfg = copy.deepcopy(moe_mla())
    del cfg["model"][key]
    if key == "torch_dtype":
        cfg["model"]["dtype"] = value
        cfg["not_mapped"]["dtype"] = "given to the program block as dtype"
    cfg["program"][field] = value
    assert getattr(harness.model_config(cfg), field) == want


def test_family_without_reference_stops_before_serving(tmp_path):
    cfg = dict(config_file("smollm-135m"), work="nosuch")
    (tmp_path / "c.json").write_text(harness.json.dumps(cfg))
    spec = harness.load_json(CHECKOUT / "BENCHMARK.json")
    spec["configs"][0]["file"] = str(tmp_path / "c.json")
    (tmp_path / "B.json").write_text(harness.json.dumps(spec))
    with pytest.raises(FileNotFoundError, match="nosuch"):
        harness.load_cell(spec["workloads"][0]["name"], tmp_path / "B.json")


# a stand-in family: its reference records every call and puts token 0
# first everywhere; its work file counts nothing
STANDIN_REFERENCE = '''
import jax.numpy as jnp
CALLS = []

def logits(model, params, tokens, quant=False):
    CALLS.append((len(tokens), quant))
    lg = jnp.zeros((len(tokens), model["vocab_size"]), jnp.float32)
    return lg.at[:, 0].set(1.0)
'''
STANDIN_WORK = '''
def decode_kernel_calls(m, contexts):
    return []

def prefill_kernel_calls(m, p):
    return []

def least_time(calls, peak):
    return 0.0

def decode_flops(m, context):
    return 0.0

def prefill_flops(m, p):
    return 0.0
'''
STANDIN_MIX = {
    "kind": "saturated",
    "engine": {"max_batch": 4, "max_len": 128, "prompt_buckets": [32, 64]},
    "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 64},
    "answer": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
    "backlog": 4, "block": 16}


@pytest.fixture
def standin(tmp_path, monkeypatch):
    """A family named ``standin``, its files in directories of their own
    that the harness is pointed at; returns its reference module."""
    for d, text in (("references", STANDIN_REFERENCE),
                    ("work", STANDIN_WORK)):
        (tmp_path / d).mkdir()
        (tmp_path / d / "standin.py").write_text(text)
    monkeypatch.setattr(harness, "REFERENCES", tmp_path / "references")
    monkeypatch.setattr(harness, "WORK", tmp_path / "work")
    return harness.reference_of({"work": "standin"})


def _req(rid, plen, served):
    return harness.Req(rid, tuple(range(1, plen + 1)), len(served), 0.0,
                       times=[1.0] * len(served), tokens=list(served))


def test_check_calls_the_reference_the_config_names(standin):
    cfg = moe_mla()
    reqs = [_req(0, 5, [0, 0, 0]), _req(1, 3, [0, 7])]
    cell = harness.Cell(name="tiny", config=cfg, mix=STANDIN_MIX, chips=1,
                        limits={}, end_to_end=[], per_layer=[])
    gap, gap_c, n = check.widest_gaps(cell.reference, cfg["model"], None,
                                      reqs, control=True)
    assert standin.CALLS == [(7, False), (7, True), (4, False), (4, True)]
    # token 7 lies 1.0 below the stand-in's best; its control agrees
    assert (gap, gap_c, n) == (1.0, 0.0, 5)


def test_control_calls_the_reference_the_config_names(standin, monkeypatch):
    cfg = moe_mla()
    cell = harness.Cell(name="tiny", config=cfg, mix=STANDIN_MIX, chips=1,
                        limits={"widest_gap": 0.5, "tokens_compared": 1},
                        end_to_end=[], per_layer=[])
    w = harness.Window(t0=0.0, t_end=2.0, t_close=2.0, compiles=0,
                       reqs={0: _req(0, 6, [0, 0])}, ticks=[],
                       backends=("pallas", "pallas"))
    monkeypatch.setattr(harness, "serve", lambda *a, **k: w)
    engine = SimpleNamespace(params=None, max_batch=4, slots=[],
                             queue=deque())
    like = {"embed": jax.ShapeDtypeStruct((512, 128), jnp.float32)}
    out = control.readings(engine, cell, like, SEED, 1.0, control=True)
    assert standin.CALLS == [(7, False), (7, True)]
    assert out["correct"] is True and out["control_correct"] is True


def test_new_family_runs_through_the_harness_unedited(standin):
    """A MoE + MLA configuration, its stand-in reference and work file,
    all found by name: a whole run of the harness serves it through the
    engine and judges it by the stand-in."""
    cfg = moe_mla()
    cell = harness.Cell(
        name="tiny", config=cfg, mix=STANDIN_MIX, chips=1,
        limits={"widest_gap": 1e9, "tokens_compared": 20},
        end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"}],
        per_layer=[])
    assert Path(cell.work.__file__) == harness.WORK / "standin.py"
    res = harness.run(cell, SEED, 1.0, False, time.perf_counter())
    assert res["check"]["tokens_compared"]["value"] >= 20
    assert {q for _, q in standin.CALLS} == {False}
    assert res["correct"] is True


def test_head_dim_wider_than_hidden_over_heads_agrees_with_reference():
    # 4 heads of 64 over a 128-wide model: head_dim is not 128 / 4
    cfg = {"name": "tiny-head-dim", "arch": "qwen2-7b", "work": "dense",
           "model": {
               "hidden_size": 128, "intermediate_size": 256,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 64, "num_hidden_layers": 2, "vocab_size": 512,
               "rms_norm_eps": 1e-6, "rope_theta": 1e6,
               "tie_word_embeddings": True, "attention_bias": True,
               "max_position_embeddings": 256, "torch_dtype": "float32"}}
    assert harness.model_config(cfg).d_head == 64
    cell = harness.Cell(name="tiny", config=cfg, mix=STANDIN_MIX, chips=1,
                        limits={"widest_gap": 0.05, "tokens_compared": 100},
                        end_to_end=[], per_layer=[])
    engine = harness.build_engine(cfg, STANDIN_MIX, SEED)
    assert engine.params["stage0"]["sub0"]["mixer"]["wq"].shape[-1] == 256
    harness.warm(engine, 512)
    w = harness.serve(engine, cell, SEED, 1.5)
    done = [r for r in w.reqs.values() if r.tokens is not None]
    picked = check.sample(done, SEED, 100)
    gap, _, n = check.widest_gaps(cell.reference, cfg["model"],
                                  engine.params, picked)
    assert n >= 100
    assert gap < 1e-4
