"""Model container: named-model pickles with the reference's naming scheme.

The reference's ``train_models_pipeline`` dumps ``<prefix>.pkl`` holding
multiple named models — {rf, threshold} × {ignore_gt} × {incl/excl hpol
runs} (names observed at docs/howto-callset-filter.md:114,139 and
test_vc_report.py:23). This registry keeps that contract: a dict-like
pickle ``{model_name: model}`` where model is a FlatForest, ThresholdModel,
or a fitted sklearn classifier (converted to FlatForest on load).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import threading
import weakref

import numpy as np

from variantcalling_tpu.models import dan as dan_mod
from variantcalling_tpu.models.dan import DanModel
from variantcalling_tpu.models.forest import FlatForest, from_sklearn
from variantcalling_tpu.models.threshold import ThresholdModel

MODEL_NAME_PATTERN = "{family}_model_{gt}_{hpol}"  # e.g. rf_model_ignore_gt_incl_hpol_runs

# Model-family resolution (docs/models.md). "forest" covers every
# tree-shaped scorer (FlatForest and anything _coerce turns into one);
# the name prefixes in MODEL_NAME_PATTERN map onto these families.
FAMILIES = ("forest", "threshold", "dan")
_NAME_PREFIX_FAMILY = {"rf": "forest", "xgb": "forest",
                       "threshold": "threshold", "dan": "dan"}


def family_of(model: object) -> str:
    """The scoring family a loaded model belongs to — the single
    spelling used by FilterContext resolution, provenance headers and
    the scoring identity."""
    if isinstance(model, DanModel):
        return "dan"
    if isinstance(model, ThresholdModel):
        return "threshold"
    return "forest"


#: id(model) -> (weakref to it, its digest). Models are unhashable mutable
#: dataclasses, so the memo is keyed on identity and a weakref both proves
#: the id still names the object digested and drops the entry with it.
_DIGEST_MEMO: dict[int, tuple[weakref.ref, str]] = {}
#: re-entrant: a collection inside the locked store can run ``forget``
_DIGEST_MEMO_LOCK = threading.RLock()


def content_digest(model: object) -> str:
    """Content address of a loaded model: equal for two models that
    compile to the same scoring program and finalize alike (two unpickles
    of one file), different when any field a program closes over differs.

    This is the model's part of the compiled-predictor cache key
    (``pipelines/filter_variants._PREDICTOR_CACHE``), so it is computed
    once per model OBJECT and a lookup is a dict probe; a model is not to
    be edited in place once it has scored."""
    memo = _DIGEST_MEMO.get(id(model))
    if memo is not None and memo[0]() is model:
        return memo[1]
    family = family_of(model)
    if isinstance(model, DanModel):
        body = dan_mod.weights_digest(model)
    elif isinstance(model, (FlatForest, ThresholdModel)):
        body = _fields_digest(model)
    else:
        raise TypeError(
            f"no content digest for a {type(model).__name__}: only loaded "
            "registry models (FlatForest, ThresholdModel, DanModel) compile")
    digest = f"{family}:{body}"
    key = id(model)

    def forget(ref, key=key):
        with _DIGEST_MEMO_LOCK:
            if _DIGEST_MEMO.get(key, (None,))[0] is ref:
                del _DIGEST_MEMO[key]

    with _DIGEST_MEMO_LOCK:
        _DIGEST_MEMO[key] = (weakref.ref(model, forget), digest)
    return digest


def _fields_digest(model: object) -> str:
    """sha256 over every dataclass field: arrays with dtype, shape and
    bytes, everything else by ``repr``."""
    h = hashlib.sha256()
    for f in dataclasses.fields(model):
        v = getattr(model, f.name)
        h.update(f.name.encode())
        if isinstance(v, np.ndarray):
            a = np.ascontiguousarray(v)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
        else:
            h.update(repr(v).encode())
    return h.hexdigest()


def family_of_name(model_name: str) -> str | None:
    """Family implied by a registry model name (``rf_model_...`` →
    forest), or None when the name follows no known pattern."""
    prefix = model_name.split("_model_", 1)[0] if "_model_" in model_name else model_name
    return _NAME_PREFIX_FAMILY.get(prefix)


def standard_model_names(families=("rf", "threshold")) -> list[str]:
    names = []
    for fam in families:
        for gt in ("ignore_gt", "use_gt"):
            for hpol in ("incl_hpol_runs", "excl_hpol_runs"):
                names.append(MODEL_NAME_PATTERN.format(family=fam, gt=gt, hpol=hpol))
    return names


def save_models(path: str, models: dict[str, object]) -> None:
    """Atomic write (tmp + rename): a crash mid-write must never leave a
    truncated pickle — checkpoint consumers resume from this file."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as fh:
        pickle.dump(models, fh)
    os.replace(tmp, path)


def load_models(path: str) -> dict[str, object]:
    if path.endswith(".json"):
        # bare xgboost JSON model file (Booster.save_model output)
        from variantcalling_tpu.models.xgb import from_xgboost_json

        return {"model": from_xgboost_json(path)}
    with open(path, "rb") as fh:
        models = pickle.load(fh)
    if not isinstance(models, dict):
        models = {"model": models}
    if isinstance(models.get("learner"), dict) and "gradient_booster" in models["learner"]:
        # the pickle IS one parsed xgboost JSON model, not a name->model map
        from variantcalling_tpu.models.xgb import from_xgboost_json

        return {"model": from_xgboost_json(models)}
    return {k: _coerce(v) for k, v in models.items()}


def load_model(path: str, model_name: str) -> object:
    models = load_models(path)
    if model_name not in models:
        # Name the missing FAMILY, not just the key: a family-explicit
        # run (VCTPU_MODEL_FAMILY=dan against a forest-only pickle) must
        # say which family the file lacks, not raise a bare KeyError.
        requested = family_of_name(model_name)
        present = sorted({family_of(m) for m in models.values()})
        hint = ""
        if requested is not None and requested not in present:
            hint = (f"; no {requested!r}-family model in this file "
                    f"(families present: {present})")
        raise KeyError(
            f"model {model_name!r} not in {sorted(models)} (file: {path}){hint}")
    return models[model_name]


def _coerce(model: object) -> object:
    if isinstance(model, (FlatForest, ThresholdModel, DanModel)):
        return model
    from variantcalling_tpu.models.xgb import from_xgboost, from_xgboost_json, looks_like_xgboost

    if looks_like_xgboost(model):
        # XGBClassifier / Booster pickle — unpicklable only when xgboost is
        # importable, in which case its own JSON dump is the exact source
        return from_xgboost(model)
    if isinstance(model, dict) and "learner" in model:
        return from_xgboost_json(model)
    if hasattr(model, "tree_") or hasattr(model, "estimators_"):
        # the fitted column order MUST ride along: the pipeline reorders
        # model features onto its own feature layout by NAME, and a
        # nameless forest scores positionally against the wrong columns
        fni = getattr(model, "feature_names_in_", None)
        return from_sklearn(model, feature_names=None if fni is None else list(fni))
    return model
