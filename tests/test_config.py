"""Typed config system tests (reference ConfigOptions/Configuration)."""

import pytest

from clonos_tpu.config import ConfigOption, Configuration, defaults


def test_defaults_and_typed_get():
    c = Configuration()
    assert c.get(defaults.DETERMINANT_SHARING_DEPTH) == -1
    assert c.get(defaults.INFLIGHT_TYPE) == "inmemory"
    c.set(defaults.DETERMINANT_SHARING_DEPTH, 2)
    assert c.get(defaults.DETERMINANT_SHARING_DEPTH) == 2


def test_type_enforcement():
    c = Configuration()
    with pytest.raises(TypeError):
        c.set(defaults.NUM_STANDBY_TASKS, "two")
    with pytest.raises(TypeError):
        c.set(defaults.NUM_STANDBY_TASKS, True)  # bool is not int here


def test_validator():
    c = Configuration()
    with pytest.raises(ValueError):
        c.set(defaults.INFLIGHT_TYPE, "bogus")
    with pytest.raises(ValueError):
        c.set(defaults.DETERMINANT_LOG_CAPACITY, 1000)  # not a power of two
    c.set(defaults.DETERMINANT_LOG_CAPACITY, 1024)


def test_int_to_float_coercion():
    c = Configuration()
    c.set(defaults.CHECKPOINT_BACKOFF_MULTIPLIER, 3)
    assert c.get(defaults.CHECKPOINT_BACKOFF_MULTIPLIER) == 3.0


def test_merge_and_raw():
    a = Configuration({"x": 1})
    b = Configuration({"x": 2, "y": 3})
    m = a.merged_with(b)
    assert m.to_dict() == {"x": 2, "y": 3}
    opt = ConfigOption("x", 0)
    assert m.get(opt) == 2


def test_every_default_option_has_a_reader():
    """An option someone can set and nothing reads is a lie in the API:
    every ``ConfigOption`` of ``config/defaults.py`` is named, as an
    attribute (``D.<NAME>``), by program code under ``clonos_tpu/``."""
    import os
    import re

    root = os.path.dirname(os.path.abspath(defaults.__file__))
    pkg = os.path.dirname(root)
    names = [n for n, v in vars(defaults).items()
             if isinstance(v, ConfigOption)]
    assert names
    source = []
    for d, _dirs, files in os.walk(pkg):
        for f in files:
            path = os.path.join(d, f)
            if f.endswith(".py") and path != defaults.__file__:
                with open(path) as fh:
                    source.append(fh.read())
    source = "\n".join(source)
    unread = [n for n in names
              if not re.search(r"\.%s\b" % n, source)]
    assert not unread, f"ConfigOptions nothing reads: {unread}"
