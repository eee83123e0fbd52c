"""Operations and bytes of each counted piece of work, one module per
family.  Every count is worked out from the configuration file's widths and
the cell's shapes; none reads the program under test."""
import importlib


def for_family(family: str):
    return importlib.import_module(f"bench.counts.{family}")
