"""Look at what an ``.xplane.pb`` holds *beside* each event: the stat
names of a plane, the metadata of its first operations with every stat,
and the device time by the program's ``hvd_tpu_*`` scope.

    python3 hvdbench/tools/dump_scopes.py <xplane.pb> [--plane /device:TPU:0] [--line "XLA Ops"] [--events 8]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    from hvdbench.reduce import program_spans, xspace

    parser = argparse.ArgumentParser()
    parser.add_argument("path")
    parser.add_argument("--plane", default="/device:TPU:0")
    parser.add_argument("--line", default="XLA Ops")
    parser.add_argument("--events", type=int, default=8)
    args = parser.parse_args()
    for name, plane in xspace.planes(args.path):
        print("PLANE", name, len(plane), "bytes")
        if name != args.plane:
            continue
        data = xspace.read_plane(plane, args.line)
        print("  stat names:", sorted(set(data["stat_names"].values())))
        print("  event metadata:", len(data["events_meta"]),
              " events on", repr(args.line), len(data["events"]))
        seen = []
        for meta_id, _ in data["events"]:
            if meta_id not in seen:
                seen.append(meta_id)
            if len(seen) >= args.events:
                break
        for meta_id in seen:
            record = data["events_meta"].get(meta_id, {})
            print("   ", meta_id, repr(record.get("name", ""))[:120])
            for k, v in record.get("stats", {}).items():
                print("       ", k, "=", repr(v)[:200])
    print(json.dumps(xspace.seconds_by_scope(
        args.path, args.plane, args.line, program_spans.SCOPE)))


if __name__ == "__main__":
    main()
