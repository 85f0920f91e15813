"""The benchmark: harness, yardstick, references, data (see README.md).

One rule for names.  A configuration or a metric's file names its family, its
FLOP or bytes function and its reader.  A name is looked up first in the table
of the file that owns the kind (``families.FAMILIES``, ``flops.FUNCTIONS``,
``readers.READERS``).  A name that is not there has the form
``<module>:<attribute>``, where ``<module>`` is a file under ``benchmark/``
(``benchmark.<...>``), and is imported when a run needs it and not before.
Anything else is refused: a configuration can never name a function of the
program as its own yardstick.
"""

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))
RULE = ("a name is one of the harness's own table or '<module>:<attribute>' with "
        "<module> a file under benchmark/ ('benchmark.<...>'), so that nothing "
        "outside the benchmark's own paths can be named as its yardstick")


def check_module(module: str, what: str = "module") -> str:
    """``module`` if it is a file (or package) under ``benchmark/``; nothing
    is imported."""
    parts = module.split(".")
    path = os.path.join(HERE, *parts[1:])
    if (parts[0] != "benchmark" or len(parts) < 2 or not all(p.isidentifier() for p in parts)
            or not (os.path.isfile(path + ".py")
                    or os.path.isfile(os.path.join(path, "__init__.py")))):
        raise ValueError(f"{what} {module!r} is refused: {RULE}")
    return module


def check_name(name: str, what: str = "name"):
    """(module, attribute) of a ``<module>:<attribute>`` name that obeys the
    rule; nothing is imported."""
    module, colon, attribute = str(name).partition(":")
    if not colon or not attribute.isidentifier():
        raise ValueError(f"{what} {name!r} is refused: {RULE}")
    return check_module(module, what), attribute


def resolve(name: str, table: dict, what: str = "name"):
    """What ``name`` names: the table's entry, else the attribute of a module
    under ``benchmark/``, imported now."""
    if name in table:
        return table[name]
    try:
        module, attribute = check_name(name, what)
    except ValueError as e:
        raise ValueError(f"{e}; the table has {sorted(table)}") from None
    return getattr(importlib.import_module(module), attribute)
