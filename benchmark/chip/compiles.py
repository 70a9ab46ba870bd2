"""What a kept run (``run.py --keep DIR``, ``JAX_LOG_COMPILES=1`` in the
environment) compiled: per query of ``job_details.json`` the executables its
tasks obtained (``xla_compiles``), how many of them came from the disk cache
(``xla_cache_hits``) and the seconds that took, stage by stage; and from
``executor.log`` the programs jax lowered, by name and first argument's
shape, with those the persistent cache then served taken off.

    python3 benchmark/chip/compiles.py DIR [DIR ...]
"""

import collections
import json
import os
import re
import sys

COMPILING = re.compile(r"Compiling (?:jit\()?([\w<>]+)\)? with global shapes and types \(?(?:ShapedArray\()?([^)]*)")
CACHE_HIT = re.compile(r"Persistent compilation cache hit for '(?:jit_)?([\w<>]+)'")


def jobs(keep_dir: str) -> None:
    with open(os.path.join(keep_dir, "job_details.json")) as f:
        details = json.load(f)
    with open(os.path.join(keep_dir, "queries.json")) as f:
        queries = json.load(f)
    print(f"  {len(queries)} queries, {len(details)} jobs; first four jobs, per stage compiled(from cache) seconds:")
    for d in sorted(details, key=lambda d: d.get("submitted_us") or 0)[:4]:
        row, total, hits, secs = [], 0, 0, 0.0
        for st in d.get("stages") or []:
            for op, vals in (st.get("metrics") or {}).items():
                if op.startswith("__") or not vals.get("xla_compiles"):
                    continue
                n, h, s = vals["xla_compiles"], vals.get("xla_cache_hits", 0), vals.get("xla_compile_ns", 0) / 1e9
                total, hits, secs = total + n, hits + h, secs + s
                row.append(f"s{st['stage_id']}:{n}({h}) {s:.1f}s")
        print(f"    {d.get('job_id')}: {total} obtained, {hits} from the cache, {secs:.1f}s  [{'  '.join(row)}]")


def programs(keep_dir: str) -> None:
    lowered, served = collections.Counter(), collections.Counter()
    with open(os.path.join(keep_dir, "executor.log"), errors="replace") as f:
        for line in f:
            m = COMPILING.search(line)
            if m:
                lowered[(m.group(1), m.group(2))] += 1
                continue
            m = CACHE_HIT.search(line)
            if m:
                served[m.group(1)] += 1
    by_name = collections.Counter()
    for (name, _), n in lowered.items():
        by_name[name] += n
    print(f"  programs lowered {sum(lowered.values())}, served by the disk cache {sum(served.values())}")
    for name, n in by_name.most_common():
        shapes = sorted(shape for (nm, shape) in lowered if nm == name)
        print(f"    {name}: lowered {n}, from cache {served.get(name, 0)}; first-argument shapes {shapes[:12]}")


if __name__ == "__main__":
    for d in sys.argv[1:]:
        print(d)
        jobs(d)
        if os.path.exists(os.path.join(d, "executor.log")):
            programs(d)
