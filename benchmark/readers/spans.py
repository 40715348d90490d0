"""Reader ``spans``: a statistic over the server's own spans
(``/v1/debug/traces``) that ended inside the traced window.

params: ``span`` the span's name; ``root`` the name of its trace's root span
(so ``qos.queue`` of a ``Search`` is told from that of a ``BatchObjects``);
``field`` (``durationMs`` or ``attributes.<key>``); ``stat`` (``median`` or
``p95``). Nothing to read -> None.
"""

from __future__ import annotations

from benchmark.harness import percentile


def read(params: dict, evidence: dict):
    values = []
    for s in evidence.get("spans", ()):
        if s["name"] != params["span"] or s.get("root") != params["root"]:
            continue
        v = s
        for key in params["field"].split("."):
            v = v.get(key) if isinstance(v, dict) else None
        if v is not None:
            values.append(float(v))
    if not values:
        return None
    return percentile(values, {"median": 0.5, "p95": 0.95}[params["stat"]])
