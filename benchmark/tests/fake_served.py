"""A stand-in for the served system: the suite's plain reference (``tpch``
unless one is given) answers the SQL text, with a fault planted where the
answer is produced.  It lets the tests
drive the rest of a run (warm-up, window, job matching, route checks, the
comparison) without a cluster."""

import itertools
import time

from benchmark import reference, suites


class _Frame:
    def __init__(self, fn):
        self.collect = fn


class FakeServed:
    def __init__(self, data_dir, chips=1, fault=None, precision="float64", mesh_devices=None, ids=True,
                 suite=None):
        self.chips, self.fault, self.precision = chips, fault, precision
        self.ids = ids  # False: contexts keep no job ids, and records fall to the time rule
        self.mesh_devices = chips if mesh_devices is None else mesh_devices
        files = None
        if fault == "half_batch":  # half of the rows left out
            files = set(range(0, 12, 2))
        elif fault == "no_exchange":  # one shard's partial result, the others never merged
            files = set(range(0, 12, 4))
        self.data = reference.Data(data_dir, files)
        self.suite = suite or suites.load("tpch")
        self.texts = {
            self.suite.render(k, p): (k, p) for k in self.suite.TABLES_OF for p in self.suite.parameter_sets(k)
        }
        self.jobs = []
        self._n = itertools.count()  # several clients answer at once

    def client(self, settings):
        served = self

        class Ctx:
            def __init__(self):
                if served.ids:
                    self._job_ids = set()  # as BallistaContext keeps them

            def sql(self, text):
                return _Frame(lambda: served._answer(text, self))

            def close(self):
                pass

        return Ctx()

    def _answer(self, text, ctx=None):
        kind, params = self.texts[text]
        t0 = time.time()
        table = self.suite.answer(self.data, kind, params, self.precision)
        if self.fault == "altered":  # an answer altered where it is produced
            name = table.column_names[-1] if kind == 6 else "revenue" if kind == 3 else "sum_charge"
            col = table.column(name).to_pylist()
            col[0] = col[0] * (1 + 1e-4)
            table = table.set_column(table.column_names.index(name), name, [col])
        elif self.fault == "swapped" and table.num_rows > 1:  # right rows, two of them the wrong way round
            table = table.take([1, 0, *range(2, table.num_rows)])
        t1 = time.time()
        n = next(self._n)
        if ctx is not None and self.ids:
            ctx._job_ids.add(f"job{n}")
        self.jobs.append({
            "job_id": f"job{n}", "state": "completed", "submitted_us": int(t0 * 1e6) + 1,
            "planning_us": 10,
            "stages": [{
                "stage_id": 1, "partitions": 1,
                "timing": {"dispatch_us": {"0": int(t0 * 1e6) + 20}, "finish_us": {"0": int(t1 * 1e6)}},
                "metrics": {
                    "MeshGangExec": {"mesh_devices": self.mesh_devices, "bridge_time_ns": 1000,
                                     "mesh_fallback": 1 if self.fault == "mesh_fallback" else 0},
                    "ScanExec": {"scan_time_ns": 2000},
                    "ShuffleWriterExec": {"xla_compiles": 0, "write_time_ns": 10},
                },
            }],
        })
        return table

    def job_details(self):
        return list(self.jobs)

    def start_trace(self):
        return {"unix_ns_before": time.time_ns()}

    def stop_trace(self):
        return {"unix_ns_before": time.time_ns()}

    def memory(self):
        return {"peak_bytes_in_use": [123]}
