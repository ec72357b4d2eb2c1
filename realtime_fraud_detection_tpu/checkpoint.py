"""Checkpoint / resume: params via orbax, host state via pickle, offsets JSON.

The reference's only real recovery mechanism is Flink checkpointing — RocksDB
operator state + Kafka offsets, 10 s interval, EXACTLY_ONCE
(FraudDetectionJob.java:112-136, docker-compose.yml:270-276); the ML service
has no model-state checkpointing at all, just immutable files + hot reload
(main.py:291-305). This module covers both roles TPU-natively (SURVEY.md §5.4):

- **device state** (model params / optimizer state — any JAX pytree) goes
  through orbax's StandardCheckpointer, sharding-aware and async-safe;
- **host state** (the scorer's velocity windows, user history ring buffers,
  entity graph, profile caches — the RocksDB analog) is pickled;
- **offsets** (the transport's committed positions — the source of truth for
  effectively-once scoring, SURVEY.md §5.4) land in a JSON manifest.

Layout:  <dir>/step_<N>/{params/, host_state.pkl, manifest.json}
with keep-N retention and a ``latest_step`` probe; ``restore`` of a partial
checkpoint (params-only, say) returns None for the missing parts.
"""

from __future__ import annotations

import dataclasses
import json
import pickle
import shutil
import time
from pathlib import Path
from typing import Any, Dict, Mapping, Optional

__all__ = [
    "Checkpoint",
    "CheckpointManager",
    "snapshot_scorer_host_state",
    "restore_scorer_host_state",
]

_MANIFEST = "manifest.json"
_HOST_STATE = "host_state.pkl"
_PARAMS = "params"

# One process-wide checkpointer: orbax Checkpointer instances own async I/O
# machinery whose finalizer (on GC of a short-lived instance) tears down a
# shared executor and breaks every later save/restore in the process.
_CHECKPOINTER = None


def _orbax_checkpointer():
    global _CHECKPOINTER
    if _CHECKPOINTER is None:
        import atexit

        import orbax.checkpoint as ocp

        _CHECKPOINTER = ocp.StandardCheckpointer()
        # flush + join orbax's async I/O threads before the interpreter
        # tears down (otherwise a save racing process exit logs
        # "cannot schedule new futures after interpreter shutdown")
        atexit.register(_close_checkpointer)
    return _CHECKPOINTER


def _close_checkpointer() -> None:
    global _CHECKPOINTER
    if _CHECKPOINTER is not None:
        try:
            _CHECKPOINTER.close()
        except Exception:  # noqa: BLE001 - best-effort at exit
            pass
        _CHECKPOINTER = None


def _derive_model_shapes(params: Any) -> Optional[Dict[str, Any]]:
    """Auto-derive restore-template shapes from a ScoringModels pytree.

    Recorded on EVERY save that stores a ScoringModels (train, run-job,
    serving), so restore never has to guess shapes from init defaults."""
    import numpy as np

    required = ("trees", "iforest", "lstm", "gnn", "bert")
    if not all(hasattr(params, k) for k in required):
        return None
    try:
        lstm_hidden = int(np.shape(params.lstm["b_gates"])[0]) // 4
        # the word embedding is a bare f32 table, or the weight-only int8
        # form {"qe": i8[rows, h], "scale": f32[rows]} (models/quant.py) —
        # the hidden size lives in the table either way
        # (the routed text encoders, models/olmoe.py and zaya.py, call it
        # embed_tokens)
        word_emb = params.bert.get("word_emb", params.bert.get("embed_tokens"))
        if isinstance(word_emb, dict):
            word_emb = word_emb["qe"]
        return {
            "trees": [int(params.trees.n_trees), int(params.trees.depth)],
            "iforest": [
                int(np.shape(params.iforest.feature)[0]),
                int(np.shape(params.iforest.path_length)[1]).bit_length() - 1,
            ],
            "bert_hidden": int(np.shape(word_emb)[1]),
            "bert_layers": len(params.bert["layers"]),
            "feature_dim": int(np.shape(params.lstm["w_gates"])[0])
            - lstm_hidden,
            "node_dim": int(np.shape(params.gnn["w_sage1"])[0]) // 2,
        }
    except (KeyError, TypeError, IndexError, AttributeError):
        return None


def _derive_quant_mode(params: Any) -> Optional[Dict[str, str]]:
    """Auto-derive the quantization-mode stamp from a ScoringModels pytree.

    Recorded on EVERY save that stores a ScoringModels (like model_shapes),
    so restore can refuse silently crossing quantization modes: a
    weight-only int8 checkpoint must never restore into an f32 scorer (or
    vice versa) without an explicit ``allow_arch_mismatch``. Only the BERT
    weight form is a PARAMETER property; the tree kernels are program
    selections, not checkpoint state."""
    if not hasattr(params, "bert"):
        return None
    from realtime_fraud_detection_tpu.models.quant import is_quantized_bert

    return {"bert_weights": "int8" if is_quantized_bert(params.bert)
            else "f32"}


def _derive_graph_mode(params: Any) -> Optional[Dict[str, str]]:
    """Auto-derive the GNN graph-mode stamp from a ScoringModels pytree.

    ``typed`` = the heterogeneous entity-graph layout (per-node-type
    projection weights, graph/ plane) vs ``bipartite`` = the original
    user↔merchant GraphSAGE. The two forms are different programs over
    different sampled tensors, so a silent cross-mode restore would
    change served scores — restore refuses it without
    ``allow_arch_mismatch``, exactly like the quant stamp."""
    if not hasattr(params, "gnn"):
        return None
    from realtime_fraud_detection_tpu.models.gnn import is_typed_gnn

    try:
        typed = is_typed_gnn(params.gnn)
    except TypeError:
        return None
    return {"gnn_nodes": "typed" if typed else "bipartite"}


@dataclasses.dataclass
class Checkpoint:
    step: int
    params: Any = None
    host_state: Any = None
    offsets: Optional[Dict[str, Any]] = None
    metadata: Optional[Dict[str, Any]] = None


class CheckpointManager:
    """Save/restore/retain checkpoints under one directory."""

    def __init__(self, directory: str | Path, keep: int = 3):
        # directory creation is deferred to save(): a restore-only caller
        # (e.g. /reload-models with a user-supplied path) must not mutate
        # the filesystem at an arbitrary location
        self.directory = Path(directory)
        self.keep = keep

    # ------------------------------------------------------------- plumbing
    @staticmethod
    def _orbax():
        return _orbax_checkpointer()

    def _step_dir(self, step: int) -> Path:
        return self.directory / f"step_{step:010d}"

    def steps(self) -> list[int]:
        out = []
        for p in self.directory.glob("step_*"):
            if (p / _MANIFEST).exists():       # incomplete saves don't count
                out.append(int(p.name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.steps()
        return steps[-1] if steps else None

    # ----------------------------------------------------------------- save
    def save(self, step: int, params: Any = None, host_state: Any = None,
             offsets: Optional[Mapping[str, Any]] = None,
             metadata: Optional[Mapping[str, Any]] = None) -> Path:
        """Write one checkpoint. The manifest is written LAST — a crash
        mid-save leaves a directory without a manifest, which ``steps()``
        ignores and the next ``save`` overwrites."""
        d = self._step_dir(step)
        if d.exists():
            shutil.rmtree(d)                   # overwrite a torn save
        d.mkdir(parents=True)
        if params is not None:
            # StandardCheckpointer wants the target dir absent
            ckptr = self._orbax()
            ckptr.save(str((d / _PARAMS).absolute()), params)
            # block until the async commit lands: the manifest below must
            # only exist once params are durable, and a short-lived process
            # (CLI train) must not exit with the commit still in flight
            ckptr.wait_until_finished()
        if host_state is not None:
            with open(d / _HOST_STATE, "wb") as f:
                pickle.dump(host_state, f, protocol=pickle.HIGHEST_PROTOCOL)
        # model_shapes is a *derived* manifest field, kept out of the
        # caller's metadata so metadata round-trips verbatim (a caller that
        # recorded shapes itself under metadata wins, for old callers).
        meta = dict(metadata) if metadata is not None else {}
        shapes = meta.get("model_shapes")
        if params is not None and shapes is None:
            shapes = _derive_model_shapes(params)
        quant_mode = meta.get("quant_mode")
        if params is not None and quant_mode is None:
            quant_mode = _derive_quant_mode(params)
        graph_mode = meta.get("graph_mode")
        if params is not None and graph_mode is None:
            graph_mode = _derive_graph_mode(params)
        manifest = {
            "step": step,
            "wall_time": time.time(),
            "has_params": params is not None,
            "has_host_state": host_state is not None,
            "offsets": dict(offsets) if offsets is not None else None,
            "metadata": meta or None,
            "model_shapes": shapes,
            "quant_mode": quant_mode,
            "graph_mode": graph_mode,
        }
        with open(d / _MANIFEST, "w") as f:
            json.dump(manifest, f, indent=1)
        self._retain()
        return d

    def _retain(self) -> None:
        steps = self.steps()
        for s in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    # -------------------------------------------------------------- restore
    def manifest(self, step: Optional[int] = None) -> Dict[str, Any]:
        """Read a checkpoint's manifest without restoring params."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {self.directory}")
        with open(self._step_dir(step) / _MANIFEST) as f:
            return json.load(f)

    def scoring_models_template(self, step: Optional[int] = None,
                                bert_config=None, feature_dim: int = 64,
                                node_dim: int = 16):
        """Restore template for a ScoringModels checkpoint.

        Tree/isolation-forest shapes vary with training flags (``train
        --trees N``); ``save`` records them in the manifest's top-level
        ``model_shapes`` field (older checkpoints carried them inside
        metadata) and this rebuilds a template with matching shapes so
        orbax's typed restore succeeds regardless of the trained sizes.
        When the manifest also records bert/feature dims, a mismatch with
        the requested dims raises a clear error instead of a cryptic orbax
        shape failure.
        """
        import jax
        import jax.numpy as jnp

        from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
        from realtime_fraud_detection_tpu.models.isolation_forest import (
            IsolationForest,
        )
        from realtime_fraud_detection_tpu.scoring import init_scoring_models
        from realtime_fraud_detection_tpu.scoring.pipeline import text_layers

        manifest = self.manifest(step)
        meta = manifest.get("metadata") or {}
        shapes = manifest.get("model_shapes") or meta.get("model_shapes") or {}
        quant_mode = manifest.get("quant_mode") or {}
        graph_mode = manifest.get("graph_mode") or {}
        want = {
            "bert_hidden": None if bert_config is None
            else bert_config.hidden_size,
            "bert_layers": None if bert_config is None
            else text_layers(bert_config),
            "feature_dim": feature_dim,
            "node_dim": node_dim,
        }
        for key, expected in want.items():
            recorded = shapes.get(key)
            if (recorded is not None and expected is not None
                    and int(recorded) != int(expected)):
                raise ValueError(
                    f"checkpoint {key}={recorded} does not match the "
                    f"server's {key}={expected}; restore with a matching "
                    f"config")
        n_trees, tree_depth = shapes.get("trees", (100, 6))
        models = init_scoring_models(
            jax.random.PRNGKey(0),
            bert_config=bert_config if bert_config is not None else TINY_CONFIG,
            feature_dim=feature_dim, node_dim=node_dim,
            n_trees=int(n_trees), tree_depth=int(tree_depth),
            # the SAVED pytree carries the typed per-node-type projection
            # leaves — orbax's typed restore needs a structurally matching
            # template (serving permission is restore_into_scorer's
            # graph-mode arch check, not a template concern)
            gnn_typed=(graph_mode.get("gnn_nodes") == "typed"))
        if "iforest" in shapes:
            n_if, if_depth = (int(v) for v in shapes["iforest"])
            models = models.replace(iforest=IsolationForest(
                feature=jnp.zeros((n_if, 2 ** if_depth - 1), jnp.int32),
                threshold=jnp.zeros((n_if, 2 ** if_depth - 1), jnp.float32),
                path_length=jnp.zeros((n_if, 2 ** if_depth), jnp.float32),
                c_psi=jnp.asarray(0.0, jnp.float32),
            ))
        if quant_mode.get("bert_weights") == "int8":
            # the SAVED pytree carries the weight-only int8 layout — orbax's
            # typed restore needs a structurally matching template (whether
            # the restoring scorer is allowed to SERVE it is
            # restore_into_scorer's arch-stamp check, not a template concern)
            from realtime_fraud_detection_tpu.models.quant import (
                quantize_bert_params,
            )

            models = models.replace(bert=quantize_bert_params(models.bert))
        return models

    def restore_into_scorer(self, scorer, step: Optional[int] = None,
                            lock=None,
                            allow_arch_mismatch: bool = False) -> Checkpoint:
        """Restore params + host state into a FraudScorer (one recipe for
        both the CLI's ``serve --checkpoint-dir`` and the serving app's
        ``/reload-models``). The step is resolved ONCE so the template and
        the restore always read the same checkpoint even while a trainer
        writes new steps; ``lock`` (the serving score lock) makes the swap
        atomic w.r.t. in-flight scoring.

        Quantization-mode arch stamp: a checkpoint whose recorded
        ``quant_mode`` crosses the scorer's configured BERT weight form
        (int8 checkpoint into an f32 scorer, or vice versa) is REFUSED
        unless ``allow_arch_mismatch`` — the two forms score differently
        (weight rounding), so a silent cross-mode restore would quietly
        change served scores. With the override, the scorer serves the
        checkpoint's actual form: an f32 restore into a quant scorer is
        quantized by ``set_models``; an int8 restore into an f32 scorer
        serves int8 (``quant_snapshot`` reads the live-params truth).
        Old checkpoints without the stamp restore leniently."""
        import contextlib

        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {self.directory}")
        ck_mode = (self.manifest(step).get("quant_mode") or {}).get(
            "bert_weights")
        want_mode = getattr(getattr(scorer, "quant", None), "bert_mode",
                            lambda: None)()
        if (ck_mode is not None and want_mode is not None
                and ck_mode != want_mode and not allow_arch_mismatch):
            raise ValueError(
                f"quantization-mode mismatch: checkpoint step {step} "
                f"records bert_weights={ck_mode!r} but the scorer is "
                f"configured for {want_mode!r}; restore with a matching "
                f"quant config or pass allow_arch_mismatch to serve the "
                f"checkpoint's form anyway")
        ck_graph = (self.manifest(step).get("graph_mode") or {}).get(
            "gnn_nodes")
        sc_graph = getattr(getattr(scorer, "sc", None), "graph_mode", None)
        want_graph = ({"typed": "typed", "bipartite": "bipartite"}
                      .get(sc_graph) if sc_graph is not None else None)
        if (ck_graph is not None and want_graph is not None
                and ck_graph != want_graph and not allow_arch_mismatch):
            raise ValueError(
                f"graph-mode mismatch: checkpoint step {step} records "
                f"gnn_nodes={ck_graph!r} but the scorer assembles "
                f"{want_graph!r} neighbor tensors; restore with a "
                f"matching graph_mode or pass allow_arch_mismatch "
                f"(stampless legacy checkpoints restore leniently)")
        template = self.scoring_models_template(
            step=step, bert_config=scorer.bert_config,
            feature_dim=scorer.sc.feature_dim, node_dim=scorer.sc.node_dim)
        ck = self.restore(step=step, params_template=template)
        with (lock if lock is not None else contextlib.nullcontext()):
            if ck.params is not None:
                scorer.set_models(ck.params)
            if ck.host_state is not None:
                restore_scorer_host_state(scorer, ck.host_state)
            # re-attach the trainer's gain importances (set_models cleared
            # them — they describe exactly the restored trees). Host-state
            # restore above already covers checkpoints that snapshot the
            # scorer; this covers params-only train checkpoints.
            imp = (ck.metadata or {}).get("feature_importances")
            if imp is not None and scorer._top_importances is None:
                try:
                    scorer.set_feature_importances(imp)
                except (ValueError, TypeError) as e:
                    import logging

                    # lenient (old/foreign manifest) but never silent: the
                    # operator must be able to see why explanations lack
                    # top_feature_importances
                    logging.getLogger(__name__).warning(
                        "checkpoint step %s: feature_importances in "
                        "manifest not attachable (%s); explanations will "
                        "omit top_feature_importances", step, e)
        return ck

    def restore(self, step: Optional[int] = None,
                params_template: Any = None) -> Checkpoint:
        """Load a checkpoint (latest if ``step`` is None).

        ``params_template`` — a pytree with the target structure/shapes
        (e.g. a freshly-initialized ScoringModels); required to restore
        params, ignored otherwise.
        """
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {self.directory}")
        d = self._step_dir(step)
        with open(d / _MANIFEST) as f:
            manifest = json.load(f)

        params = None
        if manifest["has_params"]:
            if params_template is None:
                raise ValueError(
                    "checkpoint has params; pass params_template to restore")
            params = self._orbax().restore(
                str((d / _PARAMS).absolute()), target=params_template)
        host_state = None
        if manifest["has_host_state"]:
            with open(d / _HOST_STATE, "rb") as f:
                host_state = pickle.load(f)
        return Checkpoint(
            step=manifest["step"],
            params=params,
            host_state=host_state,
            offsets=manifest.get("offsets"),
            metadata=manifest.get("metadata"),
        )


# --------------------------------------------------------------------------
# FraudScorer integration: host-state snapshot = the RocksDB analog
# --------------------------------------------------------------------------

def snapshot_scorer_host_state(scorer) -> Dict[str, Any]:
    """Pickle-able snapshot of a FraudScorer's streaming state (velocity
    windows, per-user history, entity graph/indexes, profiles, txn cache —
    everything the reference kept in Redis/RocksDB, SURVEY.md §2.5)."""
    return {
        "profiles": scorer.profiles,
        "velocity": scorer.velocity,
        "history": scorer.history,
        "graph": scorer.graph,
        "txn_cache": scorer.txn_cache,
        "users_index": scorer._users,
        "merchants_index": scorer._merchants,
        # typed entity graph (graph/ plane): only when scorer-LOCAL — a
        # partition-bundle-backed graph (stores= injection) snapshots
        # with its PartitionState, never here (the handoff path owns it)
        "typed_graph": (scorer.typed_graph
                        if getattr(scorer, "typed_graph", None) is not None
                        and not hasattr(scorer.typed_graph, "_store")
                        else None),
        "stats": dict(scorer.stats),
        # the top-10 explanation importances are scorer host state too —
        # every save/restore path round-trips them, not just the train CLI's
        # metadata (set_models during restore clears them deliberately)
        "top_importances": scorer._top_importances,
    }


def restore_scorer_host_state(scorer, state: Mapping[str, Any]) -> None:
    scorer.profiles = state["profiles"]
    scorer.velocity = state["velocity"]
    scorer.history = state["history"]
    scorer.graph = state["graph"]
    scorer.txn_cache = state["txn_cache"]
    scorer._users = state["users_index"]
    scorer._merchants = state["merchants_index"]
    typed = state.get("typed_graph")
    if (typed is not None
            and getattr(scorer, "typed_graph", None) is not None
            and not hasattr(scorer.typed_graph, "_store")):
        # restore only into a scorer-local typed graph (a partition-
        # bundle facade restores through handoff, not here); the sampler
        # keeps reading the scorer's store by reference, so swap the
        # reference it holds and drop every cached neighborhood
        scorer.typed_graph = typed
        scorer._sampler.graph = typed
        scorer._sampler._cache.clear()
        scorer._sampler._deps.clear()
    scorer.stats.update(state["stats"])
    if state.get("top_importances") is not None:
        scorer._top_importances = dict(state["top_importances"])
