"""A configuration file, as run, to the program's ``ModelConfig``.

The file holds the published ``config.json`` keys with the values that
are run. Its ``program`` section says how they reach the program:
``maps`` names the ``ModelConfig`` field (dotted for nested groups) each
key sets, ``set`` gives fields the source leaves implicit, and ``fixed``
lists values the program cannot change, which the file must state as
they are. The registry entry ``model_id`` supplies the layer pattern.

``engine`` holds the serving widths: DP groups, decode slots per group
and the cache length.
"""
from __future__ import annotations

import dataclasses
import inspect
import json
from pathlib import Path
from typing import Any, Dict

HERE = Path(__file__).resolve().parent


def load(name_or_path) -> dict:
    path = Path(name_or_path)
    if path.suffix != ".json":
        path = HERE / "configs" / f"{name_or_path}.json"
    return json.loads(path.read_text())


def _replace(obj, field: str, value: Any):
    head, _, rest = field.partition(".")
    if rest:
        return dataclasses.replace(
            obj, **{head: _replace(getattr(obj, head), rest, value)})
    if not hasattr(obj, head):
        raise KeyError(f"{type(obj).__name__} has no field {head!r}")
    return dataclasses.replace(obj, **{head: value})


def model_config(conf: dict):
    """The ``ModelConfig`` the program runs for configuration ``conf``."""
    from repro.configs import get_config

    prog = conf["program"]
    for key, want in prog.get("fixed", {}).items():
        if conf.get(key) != want:
            raise ValueError(
                f"{conf['name']}: {key}={conf.get(key)!r}, but the program "
                f"runs only {want!r}")
    updates: Dict[str, Any] = {}
    for key, fields in prog["maps"].items():
        for f in [fields] if isinstance(fields, str) else fields:
            updates[f] = conf[key]
    updates.update(prog.get("set", {}))
    cfg = get_config(conf["model_id"])
    for f, v in updates.items():
        cfg = _replace(cfg, f, v)
    dense = conf.get("first_k_dense_replace")
    if dense is not None and len(cfg.prefix_layers) != dense:
        raise ValueError(f"{conf['name']}: {dense} leading dense layers, the "
                         f"program has {len(cfg.prefix_layers)}")
    return cfg


def build_engine(conf: dict, seed: int):
    """``(model, params, engine)``: weights made from ``seed`` and the
    engine at the configuration's widths."""
    from repro.models.mesh_ctx import make_smoke_ctx
    from repro.models.transformer import build_model
    from repro.serving import FlowServeEngine
    from repro.serving.dp_group import DPGroup
    from repro.serving.kv_cache import BlockAllocator

    from bench import weights

    w = conf["engine"]
    mcfg = model_config(conf)
    model = build_model(mcfg, make_smoke_ctx())
    params = weights.make(model, seed)
    engine = FlowServeEngine(mcfg, params=params,
                             n_dp_groups=w["n_dp_groups"],
                             max_batch=w["max_batch"], max_len=w["max_len"],
                             seed=seed)
    # FlowServeEngine gives its DP groups no pool size, so each keeps
    # DPGroup's default block pool whatever its device cache holds, and
    # one scheduling pass can admit more than that pool (OutOfBlocks).
    # Each pool is sized here to its group's cache, max_batch * max_len
    # tokens, until the engine sizes it; then this stops the run.
    default = inspect.signature(DPGroup).parameters["n_kv_blocks"].default
    for dp in engine.dps:
        if dp.allocator.n_blocks != default:
            raise RuntimeError(
                "FlowServeEngine now sizes the KV block pool itself: drop "
                "the benchmark's own sizing")
        bs = dp.allocator.block_size
        dp.allocator = BlockAllocator(w["max_batch"] * w["max_len"] // bs,
                                      bs)
    return model, params, engine
