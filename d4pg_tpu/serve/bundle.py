"""Policy bundles: everything inference needs, in one directory.

A bundle decouples SERVING from TRAINING: the exporter
(``train.py --export-bundle`` or :func:`export_bundle`) packages the actor
params, the :class:`~d4pg_tpu.agent.state.D4PGConfig` that shapes the
network, the env's action bounds, and the obs-normalizer statistics from
``trainer_meta.json`` into a self-describing directory — so the serving
process reconstructs the exact acting-time data path (normalize → actor →
clip → affine to env bounds) with no Trainer, replay, env, or Orbax import
anywhere near it.

Layout::

    <bundle>/
      bundle.json        config + bounds + obs-norm stats + provenance
      actor_params.npz   actor param leaves in tree_flatten order
                         (zero-padded ``leaf_%05d`` keys, the
                         ``best_actor.npz`` discipline — sorted(files)
                         restores the order exactly)

Writes are atomic (params first, json second, each tmp+rename): a reader —
including the server's hot-reload watcher — never sees a json attesting
params that are not fully on disk. Hot reload keys on ``bundle.json``'s
mtime for exactly this reason: it is the LAST file the exporter moves into
place.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from d4pg_tpu.agent.state import D4PGConfig
from d4pg_tpu.models.critic import DistConfig

BUNDLE_VERSION = 1
PARAMS_FILE = "actor_params.npz"
META_FILE = "bundle.json"


def config_to_json(config: D4PGConfig) -> dict:
    return dataclasses.asdict(config)


def config_from_json(d: dict) -> D4PGConfig:
    """Rebuild the frozen dataclasses from their asdict form. Unknown keys
    are a hard error: a bundle written by a newer schema must fail loudly,
    not silently drop a field that changes the network."""
    d = dict(d)
    dist_d = d.pop("dist", None)
    known = {f.name for f in dataclasses.fields(D4PGConfig)}
    unknown = set(d) - known
    if unknown:
        raise ValueError(
            f"bundle agent config has unknown fields {sorted(unknown)}; "
            "re-export with this code or upgrade it"
        )
    if "hidden_sizes" in d:
        d["hidden_sizes"] = tuple(d["hidden_sizes"])
    if d.get("pixel_shape") is not None:
        d["pixel_shape"] = tuple(d["pixel_shape"])
    dist = DistConfig(**dist_d) if dist_d is not None else DistConfig()
    return D4PGConfig(dist=dist, **d)


@dataclass
class PolicyBundle:
    """A loaded bundle: the inference-time contract."""

    config: D4PGConfig
    actor_params: Any                      # numpy pytree, tree of the actor net
    action_low: np.ndarray                 # [action_dim] env-scale bounds
    action_high: np.ndarray
    obs_norm: Optional[dict]               # {"count","mean","m2"} or None
    meta: dict                             # provenance (env, step, source, …)
    path: Optional[str] = None             # directory it was loaded from

    @property
    def obs_dim(self) -> int:
        return self.config.obs_dim

    @property
    def action_dim(self) -> int:
        return self.config.action_dim


def actor_template(config: D4PGConfig):
    """A freshly-initialized actor params pytree with the bundle's shapes —
    the unflatten target for the saved leaves (and the shape validator)."""
    import jax

    from d4pg_tpu.agent.d4pg import build_networks

    if config.torso is not None:
        raise ValueError(
            "a serving bundle holds a stateless actor; a torso actor needs "
            "its session's observation window and the critic's torso "
            "(stateful serving sessions: ROADMAP B-m3)")
    actor, _ = build_networks(config)
    return actor.init(
        jax.random.PRNGKey(0), np.zeros((1, config.obs_dim), np.float32)
    )


def _save_leaves(path: str, params) -> None:
    import jax

    leaves = jax.tree_util.tree_leaves(jax.device_get(params))
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(
                f,
                **{f"leaf_{i:05d}": np.asarray(l) for i, l in enumerate(leaves)},
            )
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise


def load_params(bundle_dir: str, config: D4PGConfig):
    """Restore the actor params pytree from a bundle directory, validating
    leaf count and shapes against a template built from ``config`` (a
    silently mis-shaped load would serve garbage actions)."""
    import jax

    template = actor_template(config)
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    with np.load(os.path.join(bundle_dir, PARAMS_FILE)) as z:
        leaves = [z[k] for k in sorted(z.files)]
    if len(leaves) != len(t_leaves):
        raise ValueError(
            f"bundle has {len(leaves)} param leaves, config implies "
            f"{len(t_leaves)} — config/params mismatch"
        )
    for i, (saved, want) in enumerate(zip(leaves, t_leaves)):
        if tuple(saved.shape) != tuple(np.shape(want)):
            raise ValueError(
                f"bundle param leaf {i} has shape {tuple(saved.shape)}, "
                f"config implies {tuple(np.shape(want))}"
            )
    return jax.tree_util.tree_unflatten(treedef, leaves)


def export_bundle(
    bundle_dir: str,
    config: D4PGConfig,
    actor_params,
    *,
    action_low=None,
    action_high=None,
    obs_norm_state: Optional[dict] = None,
    meta: Optional[dict] = None,
) -> str:
    """Write a serving bundle. Bounds default to the canonical (−1, 1) box
    (pure-JAX envs act in it natively; host adapters expose their Box via
    ``NormalizeAction``)."""
    os.makedirs(bundle_dir, exist_ok=True)
    low = np.full(config.action_dim, -1.0, np.float32) if action_low is None \
        else np.asarray(action_low, np.float32).reshape(config.action_dim)
    high = np.full(config.action_dim, 1.0, np.float32) if action_high is None \
        else np.asarray(action_high, np.float32).reshape(config.action_dim)
    if not np.all(high > low):
        raise ValueError("action_high must exceed action_low elementwise")
    # params FIRST, json second (write-ordering: the json is the attestation
    # a watcher reloads on)
    _save_leaves(os.path.join(bundle_dir, PARAMS_FILE), actor_params)
    doc = {
        "bundle_version": BUNDLE_VERSION,
        "agent": config_to_json(config),
        "action_low": low.tolist(),
        "action_high": high.tolist(),
        "obs_norm": obs_norm_state,
        "meta": meta or {},
    }
    meta_path = os.path.join(bundle_dir, META_FILE)
    fd, tmp = tempfile.mkstemp(dir=bundle_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        os.replace(tmp, meta_path)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise
    return bundle_dir


def load_bundle(bundle_dir: str) -> PolicyBundle:
    meta_path = os.path.join(bundle_dir, META_FILE)
    with open(meta_path) as f:
        doc = json.load(f)
    if doc.get("bundle_version") != BUNDLE_VERSION:
        raise ValueError(
            f"bundle_version {doc.get('bundle_version')!r} unsupported "
            f"(this code reads {BUNDLE_VERSION})"
        )
    config = config_from_json(doc["agent"])
    params = load_params(bundle_dir, config)
    obs_norm = doc.get("obs_norm")
    if obs_norm is not None and len(obs_norm.get("mean", [])) != config.obs_dim:
        raise ValueError(
            f"obs_norm stats are {len(obs_norm.get('mean', []))}-dim, "
            f"config.obs_dim is {config.obs_dim}"
        )
    return PolicyBundle(
        config=config,
        actor_params=params,
        action_low=np.asarray(doc["action_low"], np.float32),
        action_high=np.asarray(doc["action_high"], np.float32),
        obs_norm=obs_norm,
        meta=doc.get("meta", {}),
        path=os.path.abspath(bundle_dir),
    )


def bundle_mtime(bundle_dir: str) -> Optional[float]:
    """mtime of the bundle's json attestation (the hot-reload watch key);
    None when absent."""
    try:
        return os.stat(os.path.join(bundle_dir, META_FILE)).st_mtime
    except FileNotFoundError:
        return None
