"""The one generator: a cell's inputs from its configuration's `data`
block, its traffic mix and `--seed`. Nothing here knows a cell by name:
`data.kind` names the recipe, generators/<kind>.py `make(data, seed)`,
which returns `(x, y)` or `(x, y, fields)`: `fields` is what
`lgb.Dataset` takes beside the label (FIELDS)."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(subdir, name):
    """The file <subdir>/<name>.py of the benchmark, found by its name
    (names may hold `.` and `-`, so not a plain import)."""
    path = os.path.join(HERE, subdir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        subdir + "_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FIELDS = ("group", "weight", "categorical_feature")


def make_data(data, seed):
    """(x, y, fields) of the configuration's recipe; `fields` is empty
    for a recipe that returns the matrix and the label alone. A key
    `lgb.Dataset` would not take is an error, never dropped."""
    x, y, *rest = load_module("generators", data["kind"]).make(data, seed)
    fields = dict(*rest)
    unknown = sorted(set(fields) - set(FIELDS))
    if unknown:
        raise ValueError(f"generator {data['kind']!r} returned unknown "
                         f"field(s) {unknown}; lgb.Dataset takes {FIELDS}")
    return x, y, fields


def train_params(config, traffic):
    """Parameters handed to the program: the configuration's, then the
    mix's (learner, sampling)."""
    return {**config["params"], **traffic.get("params", {})}
