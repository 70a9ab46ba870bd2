"""TPC-H q1, q6 and q3 over lineitem, orders and customer: the suite of every
configuration that names none.

Its parts stay in the modules that hold them: the generators in
``datagen.py``, the kinds' tables, columns, orders, parameter sets and texts
in ``queries.py``, the plain reference in ``reference.py``.  A kind is the
query's number.
"""

from benchmark import datagen
from benchmark.queries import (  # noqa: F401 - the suite's interface
    COLUMN_BYTES, COLUMNS_OF, ORDER_BY, TABLES_OF, parameter_sets, render,
)
from benchmark.reference import answer  # noqa: F401 - the suite's interface

# the order fixes each table's seed offset
TABLES = {"lineitem": datagen.gen_lineitem, "orders": datagen.gen_orders, "customer": datagen.gen_customer}


def scale(config: dict, sf=None) -> float:
    """The scale factor: the configuration's, or a rehearsal's ``sf``."""
    return float(config["scale_factor"]) if sf is None else sf


def label(kind) -> str:
    return f"q{kind}"
