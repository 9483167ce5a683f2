"""Stand-ins put under a run in place of the sound program: the
lower-precision control and the faults a cell can have.  Each has to make
`correct` come out false (tests/test_correct.py at a small size; on the chip
at the cell's own size through `run.py --control <name>`).  A benchmark run
takes none of them: its timed call is `DataFrame.collect` itself."""
from typing import Callable, Dict, Optional

import numpy as np
import pyarrow as pa


class Tamper:
    """The hooks a stand-in can take; this one changes nothing."""

    def tables(self, tables: Dict) -> Dict:
        """The tables the program is given (the reference keeps the real
        ones)."""
        return tables

    def replace(self, query: str, module, tables) -> Optional[Callable]:
        """A stand-in for the program's collect of this query, or None."""
        return None

    def answer(self, query: str, table):
        """The answer as the program hands it back."""
        return table


class Float32Money(Tamper):
    """The control: the reference in the program's place, with money held in
    float32 instead of exact integers - the step that tempts on a chip with
    no native 64-bit lanes.  Breaks the configuration's `exact_decimal`
    guarantee."""

    def replace(self, query, module, tables):
        return lambda: module.reference(tables, money=np.float32)


class HalfTheRows(Tamper):
    """Half of the batch left out: the program is given every second row of
    the largest table."""

    def tables(self, tables):
        big = max(tables, key=lambda k: tables[k].num_rows)
        keep = np.arange(0, tables[big].num_rows, 2)
        return dict(tables, **{big: tables[big].take(pa.array(keep))})


class AlteredAnswer(Tamper):
    """An answer altered where it is produced: the last cell of the last
    column moves by one unit of its last place."""

    def answer(self, query, table):
        name = table.schema.names[-1]
        cells = table[name].to_pylist()
        unit = type(cells[-1])(1) if not hasattr(cells[-1], "scaleb") \
            else cells[-1].__class__(1).scaleb(-table.schema.field(name).type.scale)
        cells[-1] = cells[-1] + unit
        return table.set_column(table.num_columns - 1, name,
                                pa.array(cells, table.schema.field(name).type))


BY_NAME = {"float32_money": Float32Money, "half_the_rows": HalfTheRows,
           "altered_answer": AlteredAnswer}
