"""Reader ``span_sum``: the sum of a field over the server's own spans that
ended inside the traced window, as a share (%) of that window.

params: ``span``, ``root`` and ``field`` as the ``spans`` reader takes them;
the field is in milliseconds. ``gc_pause_share`` is the collector's time
(``attributes.gc_ms`` of every ``interp.tick``) over the window's. No such
span in the window, or no window -> None; spans whose field sums to nothing
-> 0.0.
"""

from __future__ import annotations


def read(params: dict, evidence: dict):
    window_s = (evidence.get("trace_info") or {}).get("window_s")
    total, found = 0.0, False
    for s in evidence.get("spans", ()):
        if s["name"] != params["span"] or s.get("root") != params["root"]:
            continue
        v = s
        for key in params["field"].split("."):
            v = v.get(key) if isinstance(v, dict) else None
        if v is not None:
            total, found = total + float(v), True
    if not found or not window_s:
        return None
    return 100.0 * total / (window_s * 1e3)
