"""Objective functions: gradients/hessians from scores.

Reference: src/objective/ (regression_objective.hpp, binary_objective.hpp,
multiclass_objective.hpp, rank_objective.hpp), factory
src/objective/objective_function.cpp:9-20.

Scores and gradients are (num_class, N) device arrays; the elementwise
objectives are jitted jnp code. Lambdarank's per-query pairwise pass runs
on the device over a length-bucketed query layout (rank_device.py); the
float64 host loop stays as its accuracy reference. docs/Objectives.md.
"""

from .objectives import (
    ObjectiveFunction,
    RegressionL2loss,
    BinaryLogloss,
    MulticlassLogloss,
    LambdarankNDCG,
    create_objective,
)

__all__ = ["ObjectiveFunction", "RegressionL2loss", "BinaryLogloss",
           "MulticlassLogloss", "LambdarankNDCG", "create_objective"]
