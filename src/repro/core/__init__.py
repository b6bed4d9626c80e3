# The paper's primary contribution: the generic attraction-repulsion
# embedding formulation and the partial-Hessian optimization strategies
# (spectral direction et al.).  See DESIGN.md §1-3.
from .affinities import (
    Affinities,
    make_affinities,
    sne_affinities,
    sne_affinities_from_d2,
    sq_distances,
)
from .baselines import LBFGS, NonlinearCG
from .linesearch import LSConfig
from .objectives import (
    NORMALIZED,
    attractive_edge_terms,
    attractive_weights,
    direct_energy,
    energy,
    energy_and_grad,
    energy_and_grad_sparse,
    grad,
    gradient_weights,
    is_normalized,
    negative_pair_terms,
)
from .spectral_init import laplacian_eigenmaps
from .strategies import DiagH, FP, GD, SD, SDMinus, SparseSD, make_strategy

__all__ = [
    "Affinities", "make_affinities", "sne_affinities",
    "sne_affinities_from_d2", "sq_distances",
    "LBFGS", "NonlinearCG",
    "LSConfig",
    "NORMALIZED", "attractive_edge_terms", "attractive_weights",
    "direct_energy", "energy", "energy_and_grad", "energy_and_grad_sparse",
    "grad", "gradient_weights", "is_normalized", "negative_pair_terms",
    "laplacian_eigenmaps",
    "DiagH", "FP", "GD", "SD", "SDMinus", "SparseSD", "make_strategy",
]
