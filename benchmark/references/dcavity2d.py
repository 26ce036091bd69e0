"""Reference of the 2-D lid-driven cavity (.par name `dcavity`): the
seeded initial fields of benchmark/seedfields.py and the plain segment of
benchmark/reference.py. Every interior cell is compared."""

import reference
import seedfields


def check_covered(par: dict) -> None:
    reference.check_covered(par, "dcavity")


def field_names(par: dict) -> tuple:
    return ("u", "v", "p")


def initial_fields(par: dict, traffic: dict, seed: int):
    pert = traffic["perturbation"]
    coef = seedfields.coefficients(seed, pert["modes"])
    return seedfields.make_fields(par, coef, pert["amplitude"])


def run_segment(par: dict, fields, dtype):
    return reference.run_segment(par, fields, dtype)
