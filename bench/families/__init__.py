"""Per family: the plain reference and the operation counts, found by a
configuration's ``family``."""
import importlib


def of(cfg: dict):
    """The module ``bench/families/<family>.py`` of a configuration."""
    return importlib.import_module(f"bench.families.{cfg['family']}")
