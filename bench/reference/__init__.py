"""Plain references the benchmark decides ``correct`` against.  One module
per model family (``dense``, ``ssm``); ``common`` holds the seed laws and
the delayed Adam step; ``follow`` drives them through a training run's
first steps and through served requests."""
import importlib


def for_family(family: str):
    return importlib.import_module(f"bench.reference.{family}")
