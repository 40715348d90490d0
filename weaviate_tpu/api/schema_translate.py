"""REST schema wire format ↔ internal CollectionConfig.

The wire shape follows the reference's swagger models
(``entities/models/class.go``: ``class``, ``properties[].dataType: [..]``,
``vectorIndexType``, ``vectorIndexConfig``, ``multiTenancyConfig`` …) so
clients of the reference can talk to this server unchanged.
"""

from __future__ import annotations

from typing import Any, Optional

from weaviate_tpu.schema.config import (
    CollectionConfig,
    DataType,
    InvertedIndexConfig,
    MultiTenancyConfig,
    Property,
    QuantizerConfig,
    ReplicationConfig,
    ShardingConfig,
    Tokenization,
    VectorIndexConfig,
    quantizer_from_dict,
)

_DISTANCE_MAP = {
    "cosine": "cosine",
    "dot": "dot",
    "l2-squared": "l2-squared",
    "manhattan": "manhattan",
    "hamming": "hamming",
}


def _quantizer_from_rest(cfg: dict) -> Optional[dict]:
    """Reference vectorIndexConfig carries pq/sq/bq/rq sub-objects."""
    for kind in ("pq", "sq", "bq", "rq"):
        sub = cfg.get(kind)
        if isinstance(sub, dict) and sub.get("enabled"):
            d = {"enabled": True, "kind": kind}
            if "segments" in sub:
                d["segments"] = sub["segments"]
            if "centroids" in sub:
                d["centroids"] = sub["centroids"]
            if "trainingLimit" in sub:
                d["training_limit"] = sub["trainingLimit"]
            if "rescoreLimit" in sub:
                d["rescore_limit"] = sub["rescoreLimit"]
            return d
    return None


def _vector_index_from_rest(index_type: str, cfg: dict) -> VectorIndexConfig:
    d: dict[str, Any] = {"index_type": index_type or "hnsw"}
    d["distance"] = _DISTANCE_MAP.get(cfg.get("distance", "cosine"), "cosine")
    if "maxConnections" in cfg:
        d["max_connections"] = cfg["maxConnections"]
    if "efConstruction" in cfg:
        d["ef_construction"] = cfg["efConstruction"]
    if "ef" in cfg:
        d["ef"] = cfg["ef"]
    if "dynamicEfMin" in cfg:
        d["dynamic_ef_min"] = cfg["dynamicEfMin"]
    if "dynamicEfMax" in cfg:
        d["dynamic_ef_max"] = cfg["dynamicEfMax"]
    if "dynamicEfFactor" in cfg:
        d["dynamic_ef_factor"] = cfg["dynamicEfFactor"]
    if "flatSearchCutoff" in cfg:
        d["flat_search_cutoff"] = cfg["flatSearchCutoff"]
    if "threshold" in cfg:  # dynamic index upgrade threshold
        d["threshold"] = cfg["threshold"]
    q = _quantizer_from_rest(cfg)
    if q:
        d["quantizer"] = q
    # multi-vector (late interaction) as the reference spells it:
    # multivector: {enabled, muvera: {ksim, dprojections, repetitions}};
    # rescoreLimit = the FDE candidates the exact MaxSim rescores
    muvera = (cfg.get("multivector") or {}).get("muvera") or {}
    for rest_name, attr in (("ksim", "ksim"), ("dprojections", "dproj"),
                            ("repetitions", "repetitions")):
        if rest_name in muvera:
            d[attr] = int(muvera[rest_name])
    if "rescoreLimit" in cfg:
        d["rescore_limit"] = int(cfg["rescoreLimit"])
    if isinstance(cfg.get("rerank"), dict):
        # the fused device rerank tier (docs/modules.md), keys as
        # RerankModuleConfig has them: module, max_tokens, params
        d["rerank"] = cfg["rerank"]
    return VectorIndexConfig.from_dict(d)


def property_from_rest(p: dict) -> Property:
    """Weaviate-style property JSON → Property. Cross-refs carry the target
    class in dataType[0] (reference entities/schema crossref); classification
    and ref-filters need it back out of the schema. Shared by schema create
    and add-property so reference handling cannot drift."""
    dt = p.get("dataType", ["text"])
    dt0 = dt[0] if isinstance(dt, list) else dt
    try:
        data_type = DataType(dt0)
    except ValueError:
        # cross-references are typed by class name in the reference
        data_type = (DataType.REFERENCE if dt0 and dt0[0].isupper()
                     else DataType.TEXT)
    tok = p.get("tokenization", "word")
    try:
        tokenization = Tokenization(tok)
    except ValueError:
        tokenization = Tokenization.WORD
    return Property(
        name=p["name"],
        data_type=data_type,
        tokenization=tokenization,
        index_filterable=p.get("indexFilterable", True),
        index_searchable=p.get(
            "indexSearchable",
            data_type in (DataType.TEXT, DataType.TEXT_ARRAY),
        ),
        index_range_filters=p.get("indexRangeFilters", False),
        description=p.get("description", ""),
        target_collection=(
            dt0 if data_type == DataType.REFERENCE else ""),
    )


MUTABLE_VECTOR_FIELDS = {
    # reference hnsw/config_update.go ValidateUserConfigUpdate: the
    # traversal-time knobs are live-mutable; structural ones are not
    "ef": "ef", "dynamicEfMin": "dynamic_ef_min",
    "dynamicEfMax": "dynamic_ef_max", "dynamicEfFactor": "dynamic_ef_factor",
    "flatSearchCutoff": "flat_search_cutoff",
    "vectorCacheMaxObjects": "vector_cache_max_objects",
}

_IMMUTABLE_VECTOR_FIELDS = {
    "distance", "maxConnections", "efConstruction", "multivector",
}


def update_class_from_rest(cfg: CollectionConfig, d: dict
                           ) -> CollectionConfig:
    """Apply a class update (PUT /v1/schema/{class}) to an existing
    config, accepting only live-mutable fields (reference
    ``usecases/schema`` update validation + ``hnsw/config_update.go``).
    Raises ValueError on attempts to change immutable structure."""
    import copy

    out = copy.deepcopy(cfg)
    if d.get("class") not in (None, cfg.name):
        raise ValueError("class name is immutable")
    if "description" in d:
        out.description = d["description"] or ""
    inv = d.get("invertedIndexConfig") or {}
    bm25 = inv.get("bm25") or {}
    if "k1" in bm25:
        out.inverted_config.bm25_k1 = float(bm25["k1"])
    if "b" in bm25:
        out.inverted_config.bm25_b = float(bm25["b"])
    if "stopwords" in inv:
        preset = (inv["stopwords"] or {}).get("preset")
        if preset:
            out.inverted_config.stopwords_preset = preset
    repl = d.get("replicationConfig") or {}
    if "factor" in repl:
        out.replication.factor = int(repl["factor"])
    vic = d.get("vectorIndexConfig") or {}
    for rest_name in vic:
        if rest_name in _IMMUTABLE_VECTOR_FIELDS:
            attr = _camel_to_snake(rest_name)
            if not hasattr(out.vector_config, attr):
                # a field this config doesn't model (clients echo back
                # whole GET payloads, e.g. multivector:{enabled:false})
                # cannot conflict — ignore rather than reject the no-op
                continue
            if vic[rest_name] != getattr(out.vector_config, attr):
                raise ValueError(
                    f"vectorIndexConfig.{rest_name} is immutable")
    if "vectorIndexType" in d and \
            d["vectorIndexType"] != out.vector_config.index_type:
        raise ValueError("vectorIndexType is immutable")
    for rest_name, attr in MUTABLE_VECTOR_FIELDS.items():
        if rest_name in vic and hasattr(out.vector_config, attr):
            setattr(out.vector_config, attr, int(vic[rest_name]))
    q = vic.get("pq") or vic.get("bq") or vic.get("sq") or vic.get("rq")
    if q and out.vector_config.quantizer is not None and \
            "rescoreLimit" in q:
        out.vector_config.quantizer.rescore_limit = int(q["rescoreLimit"])
    return out


def _camel_to_snake(name: str) -> str:
    import re as _re

    return _re.sub(r"(?<!^)(?=[A-Z])", "_", name).lower()


def class_from_rest(d: dict) -> CollectionConfig:
    """Weaviate-style class JSON → CollectionConfig. Also accepts the
    internal ``to_dict`` shape (round-trip)."""
    if "name" in d and "class" not in d:
        return CollectionConfig.from_dict(d)

    props = [property_from_rest(p) for p in d.get("properties", []) or []]

    vic = d.get("vectorIndexConfig", {}) or {}
    vec_cfg = _vector_index_from_rest(d.get("vectorIndexType", "hnsw"), vic)

    named = {}
    for name, vc in (d.get("vectorConfig") or {}).items():
        named[name] = _vector_index_from_rest(
            vc.get("vectorIndexType", "hnsw"),
            vc.get("vectorIndexConfig", {}) or {},
        )

    inv = d.get("invertedIndexConfig", {}) or {}
    bm25 = inv.get("bm25", {}) or {}
    mt = d.get("multiTenancyConfig", {}) or {}
    repl = d.get("replicationConfig", {}) or {}
    shard = d.get("shardingConfig", {}) or {}

    return CollectionConfig(
        name=d["class"],
        properties=props,
        vector_config=vec_cfg,
        named_vectors=named,
        inverted_config=InvertedIndexConfig(
            bm25_k1=bm25.get("k1", 1.2),
            bm25_b=bm25.get("b", 0.75),
            stopwords_preset=(inv.get("stopwords", {}) or {}).get("preset", "en"),
            index_timestamps=inv.get("indexTimestamps", False),
            index_null_state=inv.get("indexNullState", False),
            index_property_length=inv.get("indexPropertyLength", False),
        ),
        multi_tenancy=MultiTenancyConfig(
            enabled=mt.get("enabled", False),
            auto_tenant_creation=mt.get("autoTenantCreation", False),
            auto_tenant_activation=mt.get("autoTenantActivation", False),
        ),
        replication=ReplicationConfig(
            factor=repl.get("factor", 1),
            async_enabled=repl.get("asyncEnabled", False),
        ),
        sharding=ShardingConfig(
            desired_count=shard.get("desiredCount", 1),
            virtual_per_physical=shard.get("virtualPerPhysical", 128),
        ),
        vectorizer=d.get("vectorizer", "none"),
        description=d.get("description", ""),
    )


def class_to_rest(cfg: CollectionConfig) -> dict:
    """CollectionConfig → Weaviate-style class JSON."""
    vic: dict[str, Any] = {"distance": cfg.vector_config.distance}
    vd = cfg.vector_config.to_dict()
    for src, dst in (
        ("max_connections", "maxConnections"),
        ("ef_construction", "efConstruction"),
        ("ef", "ef"),
        ("dynamic_ef_min", "dynamicEfMin"),
        ("dynamic_ef_max", "dynamicEfMax"),
        ("dynamic_ef_factor", "dynamicEfFactor"),
        ("flat_search_cutoff", "flatSearchCutoff"),
        ("threshold", "threshold"),
    ):
        if src in vd:
            vic[dst] = vd[src]
    if cfg.vector_config.index_type == "multivector":
        vic["multivector"] = {"enabled": True, "muvera": {
            "enabled": True, "ksim": vd["ksim"],
            "dprojections": vd["dproj"], "repetitions": vd["repetitions"]}}
        vic["rescoreLimit"] = vd["rescore_limit"]
    if cfg.vector_config.rerank is not None:
        vic["rerank"] = cfg.vector_config.rerank.to_dict()
    if cfg.vector_config.quantizer is not None:
        qd = cfg.vector_config.quantizer.to_dict()
        vic[qd.pop("kind")] = {"enabled": True, **{
            {"training_limit": "trainingLimit",
             "rescore_limit": "rescoreLimit"}.get(k, k): v
            for k, v in qd.items() if k != "enabled"
        }}

    props = []
    for p in cfg.properties:
        props.append({
            "name": p.name,
            # cross-refs serialize as ["TargetClass"] on the wire
            # (reference schema JSON), not the internal "cref" tag
            "dataType": [p.target_collection
                         if (p.data_type == DataType.REFERENCE
                             and p.target_collection)
                         else p.data_type.value],
            "tokenization": p.tokenization.value,
            "indexFilterable": p.index_filterable,
            "indexSearchable": p.index_searchable,
            "indexRangeFilters": p.index_range_filters,
            "description": p.description,
        })

    out = {
        "class": cfg.name,
        "description": cfg.description,
        "properties": props,
        "vectorizer": cfg.vectorizer,
        "vectorIndexType": cfg.vector_config.index_type,
        "vectorIndexConfig": vic,
        "invertedIndexConfig": {
            "bm25": {"k1": cfg.inverted_config.bm25_k1,
                     "b": cfg.inverted_config.bm25_b},
            "stopwords": {"preset": cfg.inverted_config.stopwords_preset},
        },
        "multiTenancyConfig": {
            "enabled": cfg.multi_tenancy.enabled,
            "autoTenantCreation": cfg.multi_tenancy.auto_tenant_creation,
            "autoTenantActivation": cfg.multi_tenancy.auto_tenant_activation,
        },
        "replicationConfig": {"factor": cfg.replication.factor,
                              "asyncEnabled": cfg.replication.async_enabled},
        "shardingConfig": {"desiredCount": cfg.sharding.desired_count,
                           "virtualPerPhysical": cfg.sharding.virtual_per_physical},
    }
    if cfg.named_vectors:
        out["vectorConfig"] = {
            name: {"vectorIndexType": vc.index_type,
                   "vectorIndexConfig": {"distance": vc.distance}}
            for name, vc in cfg.named_vectors.items()
        }
    return out
