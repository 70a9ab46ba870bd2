"""PR 29's width sweep: the gang stage's wall by how many partitions it
prepares side by side, in THIS process (the local engine runs q1 and q6
through ``MeshGangExec._mesh_phases`` on whatever backend jax has: the chip
on the chip host).  The width is forced through the function that reads the
usable cores, as the tests do; the program has no option for it.

    python3 benchmark/chip/gang_width.py [--sf 1.0] [--files 12] [--widths 1,2,3,4,6,8]
        [--repeats 3] [--seed 2900000001] [--out chiprun_out/pr29/sweep.jsonl]
        [--together 4 --together-widths 1,3,6]

One JSON line a (width, kind): the stage's wall and its counters in ms, the
median over ``--repeats`` after one unmeasured run a kind (compiles), and
first the host's cores as the process sees them.  Answers are compared with
width 1's, bit for bit.  The sweep lifts the program's cap on the width, so
that every asked width is the width that ran (``gang_workers`` says).  With
``--together N``: N threads each run q1 then q6 at once, as N busy task
slots would, per width: the seconds until all are done (does a pool a
slot oversubscribe the host?).
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import datagen, queries  # noqa: E402

NS = (
    "mesh_stage_time_ns", "gang_wait_ns", "gang_merge_ns", "gang_upload_ns",
    "gang_assemble_ns", "gang_step_ns", "gang_materialize_ns", "gang_scan_ns",
    "key_encode_time_ns", "gang_convert_ns", "gang_cpu_ns",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--files", type=int, default=12)
    ap.add_argument("--widths", default="1,2,3,4,6,8")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2900000001)
    ap.add_argument("--together", type=int, default=0)
    ap.add_argument("--together-widths", default="1,3,6")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "pr29", "sweep.jsonl"))
    args = ap.parse_args()
    widths = [int(w) for w in args.widths.split(",")]

    data = tempfile.mkdtemp(prefix="gang_width_")
    made = datagen.generate(data, ["lineitem"], args.sf, args.seed, args.files)

    import jax

    from arrow_ballista_tpu import SessionContext
    from arrow_ballista_tpu.parallel import mesh_stage
    from arrow_ballista_tpu.parallel.mesh_stage import MeshGangExec

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out = open(args.out, "w")

    def emit(row: dict) -> None:
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    emit({
        "affinity_cores": len(os.sched_getaffinity(0)), "cpu_count": os.cpu_count(),
        "device": jax.devices()[0].device_kind, "devices": len(jax.devices()),
        "rows": made["rows"], "files": args.files, "datagen_s": round(made["seconds"], 2),
    })

    ctx = SessionContext()
    ctx.register_parquet("lineitem", os.path.join(data, "lineitem"))
    draws = queries.Draws(args.seed, (1, 6))
    texts = {k: queries.render(k, draws.window(k, 0)) for k in (1, 6)}

    def run(kind: int) -> tuple:
        plan = ctx.sql(texts[kind]).physical_plan()
        t0 = time.perf_counter()
        answer = ctx.execute(plan)
        wall = time.perf_counter() - t0
        stack, gangs = [plan], []
        while stack:
            node = stack.pop()
            if isinstance(node, MeshGangExec):
                gangs.append(node)
            stack.extend(node.children())
        (gang,) = gangs
        return answer, wall, gang.metrics.to_dict()

    reference: dict = {}
    real_cores = mesh_stage._usable_cores
    real_cap = mesh_stage._MAX_GANG_WIDTH
    mesh_stage._MAX_GANG_WIDTH = max(widths + [real_cap])
    for width in widths:
        mesh_stage._usable_cores = lambda w=width: w
        for kind in (1, 6):
            run(kind)  # compiles, page cache
            reads = [run(kind) for _ in range(args.repeats)]
            answer = reads[0][0]
            same = reference.setdefault(kind, answer).equals(answer)
            row = {"width": width, "kind": f"q{kind}", "same_as_first_width": same,
                   "query_ms": round(statistics.median(r[1] for r in reads) * 1e3, 1),
                   "gang_workers": reads[0][2].get("gang_workers"),
                   "gang_uploads": reads[0][2].get("gang_uploads"),
                   "mesh_fallback": reads[0][2].get("mesh_fallback", 0)}
            for k in NS:
                row[k.replace("_time_ns", "_ms").replace("_ns", "_ms")] = round(
                    statistics.median(r[2].get(k, 0) for r in reads) / 1e6, 1)
            row["walls_ms"] = [round(r[2]["mesh_stage_time_ns"] / 1e6, 1) for r in reads]
            emit(row)
    if args.together:
        import threading

        def slot(results: list) -> None:
            t0 = time.perf_counter()
            for kind in (1, 6):
                run(kind)
            results.append(time.perf_counter() - t0)

        for width in [int(w) for w in args.together_widths.split(",")]:
            mesh_stage._usable_cores = lambda w=width: w
            rounds = []
            for _ in range(args.repeats + 1):
                results: list = []
                threads = [threading.Thread(target=slot, args=(results,)) for _ in range(args.together)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                rounds.append({"all_done_s": round(time.perf_counter() - t0, 3),
                               "slot_mean_s": round(statistics.mean(results), 3)})
            emit({"together": args.together, "width": width, "threads": args.together * width,
                  "rounds_after_the_first": rounds[1:]})
    mesh_stage._usable_cores, mesh_stage._MAX_GANG_WIDTH = real_cores, real_cap
    out.close()


if __name__ == "__main__":
    main()
