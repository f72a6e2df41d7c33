"""The one generator: a cell's inputs from its configuration's `data`
block, its traffic mix and `--seed`. Nothing here knows a cell by name:
`data.kind` names the recipe, generators/<kind>.py `make(data, seed)`."""

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


def make_data(data, seed):
    return load_module("generators", data["kind"]).make(data, seed)


def train_params(config, traffic):
    """Parameters handed to the program: the configuration's, then the
    mix's (learner, sampling)."""
    return {**config["params"], **traffic.get("params", {})}
