"""Entry: one ``model_zoo.prefill`` of a registered model per call.

The call goes through the program's public model path as a server makes
it: ``repro.configs.registry.get_config(<config name>)`` and the jitted
``model_zoo.prefill``, compiled ahead of the window for the one prompt
shape. It returns the last position's logits and the caches decode
continues from. The weights are the benchmark's own, made from the seed in
the published checkpoint's layout by ``bench/reference/granite_hybrid.py``
and written layer by layer into the program's parameter tree (whose
structure comes from an abstract ``model_zoo.init``): the reference makes
the same weights again and never reads the program's.

Traffic parameters (``bench/traffic/<name>.json``):

- ``op``: ``prefill``;
- ``batch``, ``prompt_len``: the prompt shape;
- ``inputs``: distinct prompts made in set-up, token ids uniform over the
  vocabulary from the seed; call i takes prompt i mod ``inputs``;
- ``decode_steps``: steps of ``model_zoo.decode_step`` the check runs
  through a checked call's caches, on continuation tokens from the seed;
- ``checked``: answers of the window checked, drawn from the seed.

The configuration gives the model's published numbers (the reference reads
them), its ``dtype`` and the limits of the comparison.
"""
from __future__ import annotations

import functools
import math
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from bench.reference import granite_hybrid as ref


# what ``control`` puts in the program's place (bench/zoo_control.py)
CONTROLS = {"fp8": {"precision": "fp8"}, "x_dt": {"skip": "x_dt"}}


def nominal_flops(config: dict, prompt_len: int, batch: int = 1) -> dict:
    """Operations of one prefill, by part: ``dense`` (2 per weight of every
    projection and MLP, per token; the head for the last position only),
    ``attention`` (causal: QK^T and PV over the lower triangle),
    ``ssd`` (the chunked SSD per head and chunk: C B^T and its product
    with x, 2 c^2 (N + P), and the carried state's two products, 4 c N P)."""
    c, s = config, prompt_len
    d, v = c["hidden_size"], c["vocab_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // hq
    h, p, n, g = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_n_groups"])
    di = c["mamba_expand"] * d
    chunk = c["mamba_chunk_size"]
    mlp = 3 * d * c["shared_intermediate_size"]
    per_kind = {"mamba": d * (2 * di + 2 * g * n + h) + di * d + mlp,
                "attention": 2 * d * hq * hd + 2 * d * hkv * hd + mlp}
    kinds = c["layer_types"]
    weights = sum(per_kind[k] for k in kinds)
    n_attn = kinds.count("attention")
    n_mamba = kinds.count("mamba")
    chunks = -(-s // chunk)
    out = {
        "dense": batch * (2.0 * s * weights + 2.0 * d * v),
        "attention": batch * n_attn * 2.0 * s * (s + 1) * hd * hq,
        "ssd": batch * n_mamba * h * chunks * (
            2.0 * chunk ** 2 * (n + p) + 4.0 * chunk * n * p),
    }
    out["total"] = sum(out.values())
    return out


def _seed_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              (seed // 2 ** 32) % 2 ** 32)


def program_layer(w: dict, kind: str, config: dict) -> dict:
    """One layer's state-dict weights (``bench/reference``'s layout) as a
    layer of the program's parameter tree (``blocks[kind]``): weights of
    shape (out, in) transposed, the MLP's input linear split into gate and
    up, the conv's (C, 1, K) as (K, C)."""
    f = config["shared_intermediate_size"]
    gate_up = w["shared_mlp.input_linear.weight"]
    p = {"ln1": {"scale": w["input_layernorm.weight"]},
         "ln2": {"scale": w["post_attention_layernorm.weight"]},
         "ffn": {"w_gate": gate_up[:f].T, "w_in": gate_up[f:].T,
                 "w_out": w["shared_mlp.output_linear.weight"].T}}
    if kind == "mamba":
        p["ssm"] = {"in_proj": w["mamba.in_proj.weight"].T,
                    "conv_w": w["mamba.conv1d.weight"][:, 0].T,
                    "conv_b": w["mamba.conv1d.bias"],
                    "a_log": w["mamba.A_log"],
                    "dt_bias": w["mamba.dt_bias"],
                    "d_skip": w["mamba.D"],
                    "norm": {"scale": w["mamba.norm.weight"]},
                    "out_proj": w["mamba.out_proj.weight"].T}
    else:
        p["attn"] = {name: w[f"self_attn.{hf}_proj.weight"].T
                     for name, hf in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                                      ("wo", "o"))}
    return p


def _same_tree(got, want, what: str) -> None:
    """Refuse a mapping that leaves a leaf of the program's tree unset."""
    shapes = [jax.tree.map(lambda t: (t.shape, jnp.dtype(t.dtype)), x)
              for x in (got, want)]
    if shapes[0] != shapes[1]:
        raise SystemExit(f"[bench] the program's {what} is not the "
                         f"published layer's: {shapes[1]} against "
                         f"{shapes[0]}")


class Entry:
    """Setting ``control`` to a key of ``CONTROLS`` (None: the program)
    puts the reference, computed one precision below (``fp8``) or with the
    old skip term (``x_dt``), in the program's place in :meth:`rows`
    (bench/zoo_control.py), so the check can be seen to fail."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        # the program's configuration first: a program without it fails
        # here, before any weight or compile
        from repro.configs import registry
        try:
            self.cfg = registry.get_config(config["name"])
        except (KeyError, ImportError) as e:
            raise SystemExit(f"[bench] the program has no configuration "
                             f"{config['name']!r}: {e!r}")
        from repro.models import model_zoo as zoo
        self.config, self.traffic, self.seed = config, traffic, seed
        self.control = None
        self._want = {}
        cfg = self.cfg
        self.dtype = jnp.dtype(config["dtype"])
        b, s = traffic["batch"], traffic["prompt_len"]
        self.steps = traffic["decode_steps"]
        sharding = SingleDeviceSharding(devices[0])
        split = nominal_flops(config, s, b)
        self.work = {"flops": split.pop("total"), "split": split,
                     "kind": "prefill"}

        t0 = time.perf_counter()
        self.k_w, k_t = jax.random.split(_seed_key(seed))
        self.params = self._weights(zoo, sharding)
        n = traffic["inputs"]
        toks = jax.jit(lambda k: jax.random.randint(
            k, (n, b, s + self.steps), 0, cfg.vocab, jnp.int32),
            out_shardings=sharding)(k_t)
        self.prompts = [toks[i, :, :s] for i in range(n)]
        self.cont = [toks[i, :, s:] for i in range(n)]
        jax.block_until_ready((self.params, self.prompts, self.cont))
        t1 = time.perf_counter()

        from repro.obs import counters
        before = counters.snapshot()
        max_len = s + self.steps
        self.fn = jax.jit(lambda p, t: zoo.prefill(
            p, {"tokens": t}, cfg, max_len=max_len)[::2]).lower(
            self.params, self.prompts[0]).compile()
        caches = jax.tree.map(
            lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype,
                                           sharding=sharding),
            self.fn.out_info[1])
        self.decode = jax.jit(lambda p, t, c, i: zoo.decode_step(
            p, t, cfg, c, i)).lower(
            self.params, self.cont[0][:, :1], caches,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
        t2 = time.perf_counter()
        # which kernels the traced prefill took (trace-time counts)
        print(f"[bench] path: counters {counters.delta(before)}",
              file=sys.stderr, flush=True)
        print(f"[bench] set-up: weights and prompts {t1 - t0:.3f} s, "
              f"compile or cache load {t2 - t1:.3f} s",
              file=sys.stderr, flush=True)

    def _weights(self, zoo, sharding):
        """The program's parameter tree, filled layer by layer from the
        reference's weights of the seed (``ref.layer_weights``): each
        kind's stack is made once in the configuration's dtype and each
        layer written into it in place, so no float32 copy of the model,
        and no second copy of a stack, is ever live."""
        config, dtype = self.config, self.dtype
        tree = jax.eval_shape(lambda k: zoo.init(k, self.cfg), self.k_w)
        tree = jax.tree.map(lambda t: jax.ShapeDtypeStruct(t.shape, dtype),
                            tree)
        kinds = config["layer_types"]
        for kind in set(kinds):
            one = jax.tree.map(lambda t: jax.ShapeDtypeStruct(t.shape[1:],
                                                              dtype),
                               tree["blocks"][kind])
            w = jax.eval_shape(lambda: ref.layer_weights(
                self.k_w, kinds.index(kind), config, dtype))
            _same_tree(jax.eval_shape(functools.partial(
                program_layer, kind=kind, config=config), w), one,
                f"{kind} layer")
        top = jax.eval_shape(lambda: ref.top_weights(self.k_w, config, dtype))
        _same_tree({"embed": {"table": top["embed_tokens.weight"]},
                    "final_norm": {"scale": top["norm.weight"]}},
                   {k: tree[k] for k in ("embed", "final_norm")},
                   "embedding or final norm")

        zeros = jax.jit(lambda: jax.tree.map(
            lambda t: jnp.zeros(t.shape, t.dtype), tree["blocks"]),
            out_shardings=sharding)
        put = jax.jit(lambda stacked, w, j, kind: jax.tree.map(
            lambda t, u: t.at[j].set(u), stacked,
            program_layer(w, kind, config)),
            static_argnames="kind", donate_argnums=0)
        blocks, seen = zeros(), {}
        for i, kind in enumerate(kinds):
            j = seen[kind] = seen.get(kind, -1) + 1
            blocks[kind] = put(blocks[kind],
                               ref.layer_weights(self.k_w, i, config, dtype),
                               j, kind=kind)
        top = ref.top_weights(self.k_w, config, dtype)
        return jax.device_put(
            {"embed": {"table": top["embed_tokens.weight"]},
             "blocks": blocks,
             "final_norm": {"scale": top["norm.weight"]}}, sharding)

    def call(self, i: int):
        """Call i, to completion on the device: (last logits, caches)."""
        return jax.block_until_ready(
            self.fn(self.params, self.prompts[i % len(self.prompts)]))

    def hlo_text(self) -> str:
        return self.fn.as_text()

    # ------------------------------ the check ------------------------------

    def rows(self, i: int, answer) -> np.ndarray:
        """The program's logit rows (batch, 1 + decode_steps, V): the
        prefill's last position, then each decode step's, through the
        prefill's caches on the continuation tokens. Under a control, the
        control's rows in their place."""
        if self.control:
            return self.reference_rows(i, **CONTROLS[self.control])
        logits, caches = answer
        cont = self.cont[i % len(self.cont)]
        s = self.traffic["prompt_len"]
        out = [np.asarray(logits[:, 0], np.float32)]
        for j in range(self.steps):
            lg, caches = self.decode(self.params, cont[:, j:j + 1], caches,
                                     jnp.int32(s + j))
            out.append(np.asarray(lg[:, 0], np.float32))
        return np.stack(out, axis=1)

    def reference_rows(self, i: int, precision: str = "highest",
                       skip: str = "x") -> np.ndarray:
        """The reference's logits at the same positions, from its full
        forward over prompt and continuation, on weights made again from
        the seed. The check's own (``highest``, ``x``) are kept per
        prompt."""
        n = len(self.prompts)
        if (precision, skip) == ("highest", "x") and i % n in self._want:
            return self._want[i % n]
        toks = jnp.concatenate([self.prompts[i % n], self.cont[i % n]], 1)
        rows = np.stack([np.asarray(ref.logits(
            self.k_w, toks[r], self.config, 1 + self.steps, self.dtype,
            precision=precision, skip=skip)) for r in range(toks.shape[0])])
        if (precision, skip) == ("highest", "x"):
            self._want[i % n] = rows
        return rows

    def compare(self, got: np.ndarray, want: np.ndarray) -> dict:
        """The RMS of the gap to the reference over the RMS of the
        reference's logits, for the prefill's row and for the decode steps'
        rows. (Not the largest gap: over 100352 logits that is an extreme
        of the rounding's spread and varies 2x between seeds, where the
        RMS varies by a few percent.)"""
        rms = np.sqrt(np.mean(np.square(want, dtype=np.float64)))
        sq = np.square(got.astype(np.float64) - want)
        return {"prefill_logit_rms_err": float(np.sqrt(sq[:, 0].mean()) / rms),
                "decode_logit_rms_err": float(np.sqrt(sq[:, 1:].mean()) / rms)}

    def check(self, kept: dict) -> list:
        """Each checked answer against the reference, worst case per
        number; returns [{name, value, limit}]."""
        limits = self.config["limits"][self.traffic["op"]]
        worst = {}
        for i in sorted(kept):
            got = self.rows(i, kept[i])
            for name, value in self.compare(got,
                                            self.reference_rows(i)).items():
                worst[name] = max(worst.get(name, -math.inf), value)
        return [{"name": k, "value": float(v), "limit": limits[k]}
                for k, v in worst.items()]
