"""Cut a profiler trace down to a small text XSpace for the tests.

  python3 bench/tools/trim_trace.py <in.xplane.pb> <out.pbtxt> \\
      [--window bench.window] [--from-ms 0] [--ms 40] [--host-events 400]

Keeps the device planes' op lines and the host planes, with only the
events that overlap ``--ms`` milliseconds of the window span from
``--from-ms`` into it (host events capped at ``--host-events``; the
window span itself is always kept, cut to that slice).  The output is what
``jax.profiler.ProfileData.from_text_proto`` reads.
"""
import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import trace  # noqa: E402


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def trim(profile, window: str, from_ms: float, ms: float,
         host_events: int) -> str:
    w0 = w1 = None
    for plane in profile.planes:
        for ln in plane.lines:
            for e in ln.events:
                if e.name == window:
                    w0 = e.start_ns + from_ms * 1e6
    if w0 is None:
        raise ValueError(f"no {window!r} span in the trace")
    w1 = w0 + ms * 1e6
    out, pid = [], 0
    for plane in profile.planes:
        device = bool(trace.DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host"):
            continue
        names, lines, lid = {}, [], 0
        budget = host_events
        for ln in plane.lines:
            if device and ln.name != trace.OPS_LINE:
                continue
            evs = []
            for e in ln.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if e.name == window:
                    a, b = w0, w1
                elif b <= w0 or a >= w1 or (not device and budget <= 0):
                    continue
                elif not device:
                    budget -= 1
                mid = names.setdefault(e.name, len(names) + 1)
                evs.append((mid, a, b))
            if not evs:
                continue
            lid += 1
            t0 = min(a for _, a, _ in evs)
            body = "".join(
                f"    events {{ metadata_id: {m} offset_ps: "
                f"{int(round((a - t0) * 1000))} duration_ps: "
                f"{int(round((b - a) * 1000))} }}\n" for m, a, b in evs)
            lines.append(f"  lines {{\n    id: {lid}\n    name: "
                         f"{_quote(ln.name)}\n    timestamp_ns: {int(t0)}\n"
                         f"{body}  }}\n")
        if not lines:
            continue
        pid += 1
        meta = "".join(f"  event_metadata {{ key: {i} value {{ id: {i} "
                       f"name: {_quote(n)} }} }}\n"
                       for n, i in names.items())
        out.append(f"planes {{\n  id: {pid}\n  name: {_quote(plane.name)}\n"
                   + "".join(lines) + meta + "}\n")
    return "".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--window", default="bench.window")
    ap.add_argument("--from-ms", type=float, default=0.0)
    ap.add_argument("--ms", type=float, default=40.0)
    ap.add_argument("--host-events", type=int, default=400)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    text = trim(ProfileData.from_file(args.src), args.window, args.from_ms,
                args.ms, args.host_events)
    Path(args.dst).write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
