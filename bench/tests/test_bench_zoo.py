"""The model cell on the CPU at a tiny size: the entry's run through
``model_zoo.prefill`` and ``decode_step``, its check against the
benchmark's own reference, the controls, and the nominal count."""
import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, zoo_control
from bench.reference import granite_hybrid as ref

CELL = "granite-4.0-h-micro.prefill.s32k"
PEAKS = run.load_json(run.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
# the published keys shrunk to a CPU size (one period of the pattern)
TINY = dict(hidden_size=128, intermediate_size=256,
            shared_intermediate_size=256, vocab_size=512,
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
            mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=64,
            num_hidden_layers=10)


def tiny_spec(monkeypatch, prompt_len=192):
    """The cell's spec at TINY, and the program's configuration shrunk to
    the same numbers in place of the registered one."""
    from repro.configs import registry
    spec = run.cell_spec(CELL, run.load_json(run.ROOT / "BENCHMARK.json"))
    config = dict(spec["config"], **TINY)
    config["layer_types"] = config["layer_types"][:10]
    cfg = dataclasses.replace(
        registry.get_config(config["name"]), n_layers=10, d_model=128,
        d_ff=256, vocab=512, n_heads=4, n_kv=2, head_dim=32, ssm_heads=8,
        ssm_head_dim=32, ssm_state=16, ssm_chunk=64)
    monkeypatch.setattr(registry, "get_config", lambda name: cfg)
    spec["config"] = config
    spec["traffic"] = dict(spec["traffic"], prompt_len=prompt_len)
    return spec


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse((run.BENCH / "reference" / "granite_hybrid.py")
                     .read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.startswith("repro")], names


def test_a_run_is_correct_and_reads_the_cell_metrics(monkeypatch):
    spec = tiny_spec(monkeypatch)
    res = run.run_cell(spec, 2 ** 33 + 7, 0.3, False, jax.devices()[:1],
                       PEAKS)
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == {"prefill_logit_rms_err",
                                   "decode_logit_rms_err"}
    assert set(res["metrics"]) == {"setup_s", "time_to_solution_s"}


def test_the_controls_read_above_the_program(monkeypatch):
    """fp8 and the D (x dt) skip term each read several times the bfloat16
    program's gap to the reference, on both numbers, through the check."""
    spec = tiny_spec(monkeypatch)
    got = zoo_control.readings(spec, 11, jax.devices()[:1],
                               zoo_control.WHATS)
    by = {r["what"]: r["compared"] for r in got}
    assert [r["prompt"] for r in got] == [1] * 3 + [0] * 3
    for what in ("fp8", "x_dt"):
        for name, c in by["program"].items():
            assert by[what][name]["value"] > 2 * c["value"], (what, by)


@pytest.mark.parametrize("what", ["fp8", "x_dt"])
def test_a_planted_control_fails_the_check(monkeypatch, what):
    """A control in the program's place comes out not correct under the
    cell's limits, where the program comes out correct."""
    spec = tiny_spec(monkeypatch)
    got = zoo_control.readings(spec, 5, jax.devices()[:1],
                               ("program", what), prompts=1)
    assert [r["correct"] for r in got] == [True, False], got


@pytest.mark.parametrize("leaf,const", [
    ("conv_b", 0.0), ("dt_bias", 0.0), ("d_skip", 1.0), ("norm", 1.0)])
def test_a_program_that_drops_a_weight_fails_the_check(monkeypatch, leaf,
                                                       const):
    """The weights are the benchmark's own: a program reading init's
    constant (conv bias 0, dt_bias 0, D 1, the gated norm's scale 1) in
    place of the weight comes out not correct."""
    spec = tiny_spec(monkeypatch)
    entry = run.load_module("entries", "zoo_prefill").Entry(
        spec["config"], spec["traffic"], 9, jax.devices()[:1])
    ssm = entry.params["blocks"]["mamba"]["ssm"]
    ssm[leaf] = jax.tree.map(lambda t: jnp.full_like(t, const), ssm[leaf])
    compared = entry.check({0: entry.call(0)})
    assert any(c["value"] > c["limit"] for c in compared), compared


def test_the_first_call_after_set_up_compiles_nothing(monkeypatch):
    spec = tiny_spec(monkeypatch)
    entry = run.load_module("entries", "zoo_prefill").Entry(
        spec["config"], spec["traffic"], 2 ** 31 + 3, jax.devices()[:1])
    with run.CompileCounter() as compiles:
        entry.call(0)
        entry.call(1)
    assert compiles.n == 0


def test_a_program_without_the_configuration_exits_at_once(monkeypatch):
    from repro.configs import registry

    def missing(name):
        raise KeyError(name)
    monkeypatch.setattr(registry, "get_config", missing)
    spec = run.cell_spec(CELL, run.load_json(run.ROOT / "BENCHMARK.json"))
    with pytest.raises(SystemExit):
        run.load_module("entries", "zoo_prefill").Entry(
            spec["config"], spec["traffic"], 1, jax.devices()[:1])


def test_nominal_flops_at_the_published_size():
    spec = run.cell_spec(CELL, run.load_json(run.ROOT / "BENCHMARK.json"))
    f = run.load_module("entries", "zoo_prefill").nominal_flops(
        spec["config"], 32768)
    s = 32768
    assert f["dense"] == pytest.approx(2 * s * 2_984_771_584
                                       + 2 * 2048 * 100352, rel=1e-12)
    assert f["attention"] == pytest.approx(4 * 2 * s * (s + 1) * 64 * 32)
    assert f["ssd"] == pytest.approx(
        36 * 64 * 128 * (2 * 256 ** 2 * 192 + 4 * 256 * 128 * 64))
    assert f["total"] == pytest.approx(2.23e14, rel=0.01)


def test_the_benchmark_reference_agrees_with_the_program_reference():
    """Two references written apart (bench/ and repro.models.reference)
    give the same float32 logits on the benchmark's weights, the program's
    through the entry's mapping into its parameter tree."""
    from repro.configs import registry
    from repro.models import reference as program_ref
    spec = run.cell_spec(CELL, run.load_json(run.ROOT / "BENCHMARK.json"))
    config = dict(spec["config"], **TINY)
    config["layer_types"] = config["layer_types"][:10]
    cfg = dataclasses.replace(
        registry.get_config(config["name"]), n_layers=10, d_model=128,
        d_ff=256, vocab=512, n_heads=4, n_kv=2, head_dim=32, ssm_heads=8,
        ssm_head_dim=32, ssm_state=16, ssm_chunk=64, dtype="float32")
    mapping = run.load_module("entries", "zoo_prefill").program_layer
    key = jax.random.PRNGKey(3)
    layers = {}
    for i, kind in enumerate(config["layer_types"]):
        layers.setdefault(kind, []).append(mapping(
            ref.layer_weights(key, i, config, jnp.float32), kind, config))
    top = ref.top_weights(key, config, jnp.float32)
    params = {"embed": {"table": top["embed_tokens.weight"]},
              "blocks": {k: jax.tree.map(lambda *t: jnp.stack(t), *v)
                         for k, v in layers.items()},
              "final_norm": {"scale": top["norm.weight"]}}
    toks = jax.random.randint(jax.random.PRNGKey(4), (100,), 0, 512)
    want = np.asarray(program_ref.forward(params, toks[None], cfg))[0]
    got = np.asarray(ref.logits(key, toks, config, 100, jnp.float32))
    rms = np.sqrt(np.mean(want ** 2))
    assert np.max(np.abs(got - want)) / rms < 1e-5


def test_the_weights_vary_where_init_has_constants():
    spec = run.cell_spec(CELL, run.load_json(run.ROOT / "BENCHMARK.json"))
    config = dict(spec["config"], **TINY)
    w = ref.layer_weights(jax.random.PRNGKey(0), 0, config, jnp.float32)
    dt = jax.nn.softplus(w["mamba.dt_bias"])
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1
    a = jnp.exp(w["mamba.A_log"])
    assert 1 <= float(a.min()) and float(a.max()) <= 16
    for name in ("mamba.conv1d.bias", "mamba.D", "mamba.norm.weight",
                 "input_layernorm.weight", "post_attention_layernorm.weight"):
        assert float(jnp.std(w[name])) > 0.1, name


def test_fp8_rounding_is_e4m3():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(4096) * 3,
                    jnp.float32)
    scale = jnp.max(jnp.abs(x)) / ref.E4M3_MAX
    want = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    np.testing.assert_allclose(ref._e4m3(x), want, rtol=1e-6, atol=0)


@pytest.mark.parametrize("name,value", [
    ("matmul_s.prefill", 0.8 / 2), ("other_s.prefill", 0.3 / 2),
    ("mfu.prefill", 100 * 2.2e14 * 2 / 2.0 / 197e12),
    ("idle_share.prefill", 25.0)])
def test_per_layer_readers(name, value):
    trace = {"window_s": 2.0, "busy_s": 1.5, "calls": 2,
             "class_s": {"matmul": 0.8, "collective": 0.0, "other": 0.3}}
    record = {"calls": 2, "window_s": 2.0, "latencies": [1.0, 1.0],
              "chips": 1, "peak": PEAKS, "work": {"flops": 2.2e14},
              "trace": trace}
    assert run.load_module("metrics", name).read(record) == pytest.approx(
        value)
