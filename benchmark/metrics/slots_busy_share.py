"""Task time over the executor's slot time: every task's dispatch to finish
as the scheduler stamps them (``jobstats.summarize``'s ``task_us``), summed
over the jobs of ALL the window's queries (a query lies in the window from
its submit to its return, and so do its tasks), over
``executor_task_slots`` x the window's seconds.  Near 100 the slots are what
is full; well under it, queries wait on something else (planning, the poll
step between a task's end and the next hand-out, the client).  The
scheduler's interval holds the executor's poll step at both ends, so a slot
is counted busy while its finished task waits to be reported."""

UNIT, BETTER, SOURCE = "%", "higher", "program_span"
LAYER, MOVES = "executor", "scan_rows_rate"


def read(run):
    slots = int(((run.get("config") or {}).get("cluster") or {}).get("executor_task_slots") or 0)
    jobs = [q["job"] for q in run["window_all"] if q.get("job")]
    task_us = sum(st.get("task_us", 0) for j in jobs for st in j["stages"])
    if not slots or not run.get("window_s") or not task_us:
        return None
    return 100.0 * task_us / 1e6 / (slots * run["window_s"])
