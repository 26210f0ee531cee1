"""Look at a trace by hand: every plane and line of an ``.xplane.pb``
with its event count, the first events of each line with all their
stats, and optionally the reduced rows of the first N milliseconds as
JSON lines (how ``reduce/sample_events.jsonl`` was recorded).

    python3 hvdbench/tools/dump_trace.py <xplane.pb> [--rows out.jsonl --ms 40 --spans a,b]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    import jax

    from hvdbench.reduce import xplane

    parser = argparse.ArgumentParser()
    parser.add_argument("path")
    parser.add_argument("--events", type=int, default=6)
    parser.add_argument("--rows")
    parser.add_argument("--ms", type=float, default=40.0)
    parser.add_argument("--spans", default="")
    args = parser.parse_args()
    data = jax.profiler.ProfileData.from_file(args.path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            if plane.name.startswith("/device") or line.name == "python":
                for ev in events[:args.events]:
                    print("     ", repr(ev.name), ev.start_ns, ev.duration_ns,
                          {k: str(v)[:160] for k, v in ev.stats})
    if args.rows:
        rows = xplane.load_events(args.path, args.spans.split(","))
        t0 = min(r["start_ns"] for r in rows
                 if xplane.DEVICE_PLANE.match(r["plane"]))
        kept = [r for r in rows if t0 - 5e6 <= r["start_ns"]
                < t0 + args.ms * 1e6]
        for r in kept:   # a name is the whole HLO text: cut it short,
            # but keep a custom call down to its target
            r["name"] = r["name"][:420 if "custom-call" in r["name"][:200]
                                  else 110]
        with open(args.rows, "w") as f:
            for r in kept:
                f.write(json.dumps(r) + "\n")
        print("wrote", len(kept), "rows to", args.rows)


if __name__ == "__main__":
    main()
