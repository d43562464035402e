"""BY001: interprocedural dispatcher-bypass lint over the model/serving layer.

Every GEMM-shaped contraction in this repo is supposed to flow through
:func:`repro.tune.dispatch.resolve` so the policy/registry machinery can
route it onto the tuned Pallas path. The model zoo and the hand-rolled
attention/SSD kernels predate that discipline: their ``dot_general``s are
raw. This lint makes the debt *visible and monotone* instead of silent -
it traces the real entry points (``zoo.forward`` / ``zoo.decode_step``
per architecture family, the serving prefill path, and the two standalone
kernels), walks every jaxpr including Pallas kernel bodies, and
attributes each raw contraction to its source site. Sites living under
:data:`repro.tune.dispatch.DISPATCHED_MODULES` are dispatched by
construction; everything else is a bypass and must appear on the
committed burn-down allowlist (``bypass_allowlist.json``) with a reason.
A *new* bypass site fails CI; deleting an entry as code migrates onto the
dispatcher is the burn-down.

Traces run *without* x64 (models use int32 tokens and run in their
declared dtype), unlike the BLAS lint mode in ``report._trace``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import rules
from repro.analysis.jaxpr_lint import iter_eqns
from repro.analysis.rules import Finding, make_finding

# the contraction primitives the dispatcher exists to route
CONTRACTION_PRIMITIVES = ("dot_general", "conv_general_dilated")

# one representative architecture per model family
BYPASS_ARCHS = ("gemma-7b", "whisper-small", "mamba2-130m", "hymba-1.5b",
                "internvl2-1b", "qwen3-moe-235b-a22b")

DEFAULT_ALLOWLIST_PATH = os.path.join(os.path.dirname(__file__),
                                      "bypass_allowlist.json")


# ------------------------------ entry points --------------------------------

def _reduced(arch: str):
    import dataclasses as _dc
    from repro.configs import registry
    from repro.launch.train import reduce_config
    cfg = reduce_config(registry.get_config(arch), layers=2, d_model=64,
                        vocab=128, heads=4)
    return _dc.replace(cfg, accum_steps=1, dtype="float32")


def _init(cfg):
    from repro.models import model_zoo as zoo
    return zoo.init(jax.random.PRNGKey(0), cfg)


def _batch(cfg, batch: int = 4, seq: int = 16):
    from repro.data.pipeline import DataConfig, make_batch
    return make_batch(cfg, DataConfig(vocab=cfg.vocab, global_batch=batch,
                                      seq_len=seq), 0)


def _forward_builder(arch: str):
    def build():
        from repro.models import model_zoo as zoo
        cfg = _reduced(arch)
        params, batch = _init(cfg), _batch(cfg)

        def fn(p, b):
            return zoo.forward(p, b, cfg, use_pallas=False)
        return fn, (params, batch), {}
    return build


def _decode_builder(arch: str):
    def build():
        from repro.models import model_zoo as zoo
        cfg = _reduced(arch)
        params = _init(cfg)
        b = 2
        memory = None
        if cfg.family == "encdec":
            memory = jax.random.normal(jax.random.PRNGKey(1),
                                       (b, 8, cfg.d_model), jnp.float32)
        caches = zoo.init_caches(params, cfg, b, 24, memory=memory,
                                 dtype=jnp.float32)
        tok = jnp.zeros((b, 1), jnp.int32)

        def fn(p, t, c):
            return zoo.decode_step(p, t, cfg, c, jnp.int32(0))
        return fn, (params, tok, caches), {}
    return build


def _serve_builder():
    def build():
        # mirrors launch/serve.py's compute path exactly
        from repro.models import model_zoo as zoo
        cfg = _reduced("mamba2-130m")
        params, batch = _init(cfg), _batch(cfg)

        def fn(p, b):
            return zoo.prefill(p, b, cfg, use_pallas=False)
        return fn, (params, batch), {}
    return build


def _attention_builder():
    def build():
        from repro.kernels.flash_attention import attention
        r = np.random.default_rng(0)
        q, k, v = (jnp.asarray(r.standard_normal((2, 2, 32, 16)),
                               jnp.float32) for _ in range(3))

        def fn(q_, k_, v_):
            return attention(q_, k_, v_, interpret=True)
        return fn, (q, k, v), {}
    return build


def _ssd_builder():
    def build():
        from repro.kernels.ssd_scan import ssd_scan
        r = np.random.default_rng(0)
        x = jnp.asarray(r.standard_normal((2, 2, 32, 4)), jnp.float32)
        a_log = jnp.asarray(-np.abs(r.standard_normal((2, 2, 32))),
                            jnp.float32)
        B = jnp.asarray(r.standard_normal((2, 2, 32, 4)), jnp.float32)
        C = jnp.asarray(r.standard_normal((2, 2, 32, 4)), jnp.float32)

        def fn(x_, a_, b_, c_):
            return ssd_scan(x_, a_, b_, c_, interpret=True)
        return fn, (x, a_log, B, C), {}
    return build


def default_entries() -> List[Tuple[str, Callable]]:
    """(name, builder) per lintable entry point; builders are lazy so one
    broken family cannot stop the others from being collected."""
    entries: List[Tuple[str, Callable]] = []
    for arch in BYPASS_ARCHS:
        entries.append((f"zoo.forward[{arch}]", _forward_builder(arch)))
        entries.append((f"zoo.decode_step[{arch}]", _decode_builder(arch)))
    entries.append(("serve.prefill", _serve_builder()))
    entries.append(("kernels.flash_attention", _attention_builder()))
    entries.append(("kernels.ssd_scan", _ssd_builder()))
    return entries


# --------------------------- site classification ----------------------------

def _site_of(eqn) -> Optional[str]:
    """``repro/<path>.py:<function>`` for one eqn, or None if unknown."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
    except Exception:
        frame = None
    if frame is None:
        return None
    path = str(frame.file_name).replace("\\", "/")
    idx = path.rfind("/repro/")
    if idx >= 0:
        path = path[idx + 1:]
    return f"{path}:{frame.function_name}"


def _is_dispatched(site: str) -> bool:
    from repro.tune.dispatch import DISPATCHED_MODULES
    path = site.split(":", 1)[0]
    return any(path.startswith(p) for p in DISPATCHED_MODULES)


def collect_bypass_sites(entries: Optional[Sequence[Tuple[str, Callable]]]
                         = None, progress: Optional[Callable] = None
                         ) -> "Tuple[OrderedDict, List[Dict]]":
    """Trace every entry and attribute its raw contractions.

    Returns ``(sites, cases)``: ``sites`` maps each *bypass* site key
    (``repro/<file>:<function>``) to ``{"primitives", "count",
    "entries"}``; ``cases`` records per-entry totals (including entries
    that failed to build, so a broken family is visible, not silent).
    """
    entries = default_entries() if entries is None else list(entries)
    sites: "OrderedDict[str, Dict]" = OrderedDict()
    cases: List[Dict] = []
    for name, build in entries:
        if progress is not None:
            progress(name)
        try:
            fn, args, kw = build()
            closed = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
        except Exception as exc:
            cases.append({"entry": name, "error":
                          f"{type(exc).__name__}: {exc}"})
            continue
        contractions = bypasses = 0
        for eqn, _ in iter_eqns(closed.jaxpr):
            if eqn.primitive.name not in CONTRACTION_PRIMITIVES:
                continue
            contractions += 1
            site = _site_of(eqn) or f"<unknown>:{eqn.primitive.name}"
            if _is_dispatched(site):
                continue
            bypasses += 1
            rec = sites.setdefault(site, {"primitives": set(), "count": 0,
                                          "entries": set()})
            rec["primitives"].add(eqn.primitive.name)
            rec["count"] += 1
            rec["entries"].add(name)
        cases.append({"entry": name, "contractions": contractions,
                      "bypasses": bypasses})
    for rec in sites.values():
        rec["primitives"] = sorted(rec["primitives"])
        rec["entries"] = sorted(rec["entries"])
    return sites, cases


# ------------------------------- allowlist ----------------------------------

def load_bypass_allowlist(path: Optional[str] = DEFAULT_ALLOWLIST_PATH
                          ) -> Dict[str, str]:
    """``{site: reason}`` from the burn-down file; registry convention.

    Missing file -> silently empty (cold start: every bypass fires).
    Corrupt / wrong-schema file -> ``RuntimeWarning`` once per path and
    treated as empty, so breakage re-fires findings, never hides one.
    """
    if path is None or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            raw = json.load(f)
        if int(raw.get("schema_version", -1)) != rules.SCHEMA_VERSION:
            raise ValueError(f"schema_version {raw.get('schema_version')!r}"
                             f" != {rules.SCHEMA_VERSION}")
        if raw.get("rule") != "BY001":
            raise ValueError(f"rule {raw.get('rule')!r} != 'BY001'")
        return {str(e["site"]): str(e.get("reason", ""))
                for e in raw["sites"]}
    except Exception as exc:
        if path not in rules._warned_paths:
            rules._warned_paths.add(path)
            warnings.warn(f"bypass allowlist {path!r} is corrupt ({exc}); "
                          "treating as empty", RuntimeWarning, stacklevel=2)
        return {}


def save_bypass_allowlist(sites: Dict[str, Dict], path: str,
                          reason: str = "pre-dispatcher site; burn down"
                          ) -> str:
    """Write the burn-down file for the current bypass set."""
    payload = {"schema_version": rules.SCHEMA_VERSION, "rule": "BY001",
               "sites": [{"site": s, "reason": reason,
                          "primitives": info["primitives"],
                          "entries": info["entries"]}
                         for s, info in sorted(sites.items())]}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


# --------------------------------- driver -----------------------------------

def lint_bypass(entries: Optional[Sequence[Tuple[str, Callable]]] = None,
                allowlist: Optional[str] = DEFAULT_ALLOWLIST_PATH,
                progress: Optional[Callable] = None):
    """BY001 over the model/serving/kernel entry points -> AnalysisReport.

    One finding per unique bypass site; sites on the committed allowlist
    land in ``report.suppressed`` (tagged ``allowlist:<path>``), so
    ``report.ok`` fails exactly when a *new* bypass appears.
    """
    from repro.analysis.report import AnalysisReport
    sites, cases = collect_bypass_sites(entries, progress=progress)
    allowed = load_bypass_allowlist(allowlist)
    active: List[Finding] = []
    suppressed: List[Finding] = []
    for site, info in sites.items():
        f = make_finding(
            "BY001", f"raw {'/'.join(info['primitives'])} at {site} "
            f"({info['count']} eqn(s), reachable from "
            f"{', '.join(info['entries'])}) never passes "
            "tune.dispatch.resolve",
            routine=info["entries"][0], location=site,
            case={"entries": info["entries"]})
        if site in allowed:
            suppressed.append(dataclasses.replace(
                f, suppressed=True, suppressed_by=f"allowlist:{allowlist}"))
        else:
            active.append(f)
    return AnalysisReport("dispatcher-bypass", cases, active, suppressed)
