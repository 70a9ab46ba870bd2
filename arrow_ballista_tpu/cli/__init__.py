"""Interactive SQL CLI.

Counterpart of the reference's ``ballista-cli`` crate
(``ballista-cli/src/main.rs:33-120``, ``command.rs:35-183``,
``exec.rs:35-170``, ``context.rs``): a readline REPL that runs either
*local* (in-proc single-node engine, like the reference's DataFusion mode)
or *remote* against a scheduler (``--host``/``--port``).  Backslash
commands mirror the reference's Command enum: ``\\q`` quit, ``\\?``/``\\h``
help, ``\\d`` list tables, ``\\d NAME`` describe, ``\\quiet [on|off]``,
``\\pset [format NAME]``, plus file execution via ``-f`` and ``-e``.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import pyarrow as pa

FORMATS = ("table", "csv", "tsv", "json", "nd-json")


class PrintOptions:
    def __init__(self, fmt: str = "table", quiet: bool = False):
        self.format = fmt
        self.quiet = quiet

    def print_table(self, tbl: pa.Table, elapsed_s: float) -> None:
        out = sys.stdout
        if self.format == "table":
            out.write(_ascii_table(tbl) + "\n")
        elif self.format in ("csv", "tsv"):
            sep = "," if self.format == "csv" else "\t"
            out.write(sep.join(tbl.schema.names) + "\n")
            for row in _iter_rows(tbl):
                out.write(sep.join("" if v is None else str(v) for v in row) + "\n")
        elif self.format == "json":
            import json

            out.write(json.dumps(tbl.to_pylist(), default=str) + "\n")
        elif self.format == "nd-json":
            import json

            for rec in tbl.to_pylist():
                out.write(json.dumps(rec, default=str) + "\n")
        if not self.quiet:
            out.write(
                f"{tbl.num_rows} row(s) in set. Query took {elapsed_s:.3f} seconds.\n"
            )
        out.flush()


def _iter_rows(tbl: pa.Table):
    cols = [c.to_pylist() for c in tbl.columns]
    for i in range(tbl.num_rows):
        yield [c[i] for c in cols]


def _ascii_table(tbl: pa.Table, max_rows: int = 1000) -> str:
    names = tbl.schema.names
    rows = [
        ["" if v is None else str(v) for v in row]
        for _, row in zip(range(max_rows), _iter_rows(tbl))
    ]
    widths = [len(n) for n in names]
    for row in rows:
        for i, v in enumerate(row):
            widths[i] = max(widths[i], len(v))
    sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
    lines = [sep]
    lines.append(
        "|" + "|".join(f" {n:<{w}} " for n, w in zip(names, widths)) + "|"
    )
    lines.append(sep)
    for row in rows:
        lines.append(
            "|" + "|".join(f" {v:<{w}} " for v, w in zip(row, widths)) + "|"
        )
    lines.append(sep)
    if tbl.num_rows > max_rows:
        lines.append(f"... {tbl.num_rows - max_rows} more row(s)")
    return "\n".join(lines)


HELP = """\
\\q                 quit
\\? or \\h           this help
\\d                 list tables
\\d NAME            describe table NAME
\\quiet [on|off]    toggle row-count/timing footer
\\pset [format F]   set output format: table csv tsv json nd-json
Any other input is executed as SQL (terminate with ;)."""


class Repl:
    def __init__(self, ctx, opts: PrintOptions):
        self.ctx = ctx
        self.opts = opts

    # ------------------------------------------------------------ commands
    def handle_command(self, line: str) -> bool:
        """Returns False when the REPL should exit."""
        parts = line.strip().split()
        cmd, args = parts[0], parts[1:]
        if cmd in ("\\q", "\\quit"):
            return False
        if cmd in ("\\?", "\\h", "\\help"):
            print(HELP)
        elif cmd == "\\d":
            if args:
                self.run_sql(f"SHOW COLUMNS FROM {args[0]}")
            else:
                self.run_sql("SHOW TABLES")
        elif cmd == "\\quiet":
            if args:
                self.opts.quiet = args[0].lower() == "on"
            print(f"quiet mode {'on' if self.opts.quiet else 'off'}")
        elif cmd == "\\pset":
            if len(args) == 2 and args[0] == "format":
                if args[1] not in FORMATS:
                    print(f"unknown format {args[1]!r}; one of {FORMATS}")
                else:
                    self.opts.format = args[1]
            else:
                print(f"format: {self.opts.format}")
        else:
            print(f"unknown command {cmd!r}; \\? for help")
        return True

    def run_sql(self, sql: str) -> bool:
        """Returns False on error (REPL stays alive; batch mode exits 1)."""
        t0 = time.perf_counter()
        try:
            tbl = self.ctx.sql(sql).collect()
        except Exception as e:  # surface engine errors, keep the REPL alive
            print(f"Error: {e}")
            return False
        self.opts.print_table(tbl, time.perf_counter() - t0)
        return True

    # ---------------------------------------------------------------- loop
    def run(self) -> None:
        try:
            import readline  # noqa: F401 (line editing side effect)
        except ImportError:
            pass
        buf: list[str] = []
        while True:
            prompt = "ballista> " if not buf else "       -> "
            try:
                line = input(prompt)
            except EOFError:
                print()
                break
            except KeyboardInterrupt:
                buf.clear()
                print()
                continue
            if not buf and line.strip().startswith("\\"):
                if not self.handle_command(line):
                    break
                continue
            if not line.strip():
                continue
            buf.append(line)
            joined = "\n".join(buf)
            if joined.rstrip().endswith(";"):
                buf.clear()
                self.run_sql(joined.rstrip().rstrip(";"))


def split_statements(text: str) -> list:
    """Split on ';' outside of single/double-quoted literals (a plain
    ``text.split(';')`` would corrupt ``SELECT 'a;b'``)."""
    stmts: list[str] = []
    buf: list[str] = []
    quote: Optional[str] = None
    for ch in text:
        if quote is not None:
            buf.append(ch)
            if ch == quote:
                quote = None
        elif ch in ("'", '"'):
            quote = ch
            buf.append(ch)
        elif ch == ";":
            stmts.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if "".join(buf).strip():
        stmts.append("".join(buf))
    return [s for s in stmts if s.strip()]


def exec_file(ctx, path: str, opts: PrintOptions) -> bool:
    """Non-interactive file execution (reference: exec.rs file mode).
    Returns False if any statement failed."""
    with open(path) as f:
        text = f.read()
    repl = Repl(ctx, opts)
    ok = True
    for stmt in split_statements(text):
        ok = repl.run_sql(stmt) and ok
    return ok


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        "ballista-tpu-cli", description="Ballista-TPU interactive SQL shell"
    )
    ap.add_argument("--host", default=None, help="scheduler host (remote mode)")
    ap.add_argument("--port", type=int, default=50050, help="scheduler port")
    ap.add_argument(
        "-p", "--data-path", default=None, help="chdir here before running"
    )
    ap.add_argument("-f", "--file", action="append", default=[],
                    help="run SQL from file(s) and exit")
    ap.add_argument("-e", "--command", action="append", default=[],
                    help="run the given SQL command(s) and exit")
    ap.add_argument("--format", default="table", choices=FORMATS)
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.data_path:
        import os

        os.chdir(args.data_path)

    if args.host:
        from ..client.context import BallistaContext

        ctx = BallistaContext.remote(args.host, args.port)
        mode = f"remote scheduler {args.host}:{args.port}"
    else:
        from ..context import SessionContext

        ctx = SessionContext()
        mode = "local mode"

    opts = PrintOptions(args.format, args.quiet)
    if args.file or args.command:
        ok = True
        for path in args.file:
            ok = exec_file(ctx, path, opts) and ok
        repl = Repl(ctx, opts)
        for sql in args.command:
            for stmt in split_statements(sql):
                ok = repl.run_sql(stmt) and ok
        if not ok:
            sys.exit(1)
        return
    print(f"Ballista-TPU CLI ({mode}). \\? for help, \\q to quit.")
    Repl(ctx, opts).run()


if __name__ == "__main__":
    main()
