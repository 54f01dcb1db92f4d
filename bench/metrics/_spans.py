"""Self time of the job's ``repro.obs`` spans, per window round.

The job's tracer writes Chrome trace events (``trace.json``, times in
microseconds on the host clock).  A span's self time is its duration less
the part of it that spans nested in it on the same track cover.
"""


def self_times(spans: list, name: str) -> list[tuple[dict, float]]:
    by_track: dict = {}
    for s in spans:
        by_track.setdefault((s["pid"], s["tid"]), []).append(s)
    out = []
    for s in spans:
        if s["name"] != name:
            continue
        start, end = s["ts"], s["ts"] + s["dur"]
        covered = []
        for c in by_track[(s["pid"], s["tid"])]:
            if c is not s and c["ts"] >= start and c["ts"] + c["dur"] <= end and c["dur"] < s["dur"]:
                covered.append((c["ts"], c["ts"] + c["dur"]))
        covered.sort()
        inside, cursor = 0.0, start
        for a, b in covered:
            a = max(a, cursor)
            if b > a:
                inside += b - a
                cursor = b
        out.append((s, (s["dur"] - inside) / 1e3))  # ms
    return out


def round_spans(spans: list, rounds: list[int]) -> dict[int, dict]:
    wanted = set(rounds)
    return {
        s["args"]["round"]: s
        for s in spans
        if s["name"] == "round" and s.get("args", {}).get("round") in wanted
    }
