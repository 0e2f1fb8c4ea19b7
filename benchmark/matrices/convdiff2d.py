"""Upwind 5-point 2-D convection–diffusion on an nx × nx grid, Dirichlet
boundaries (the model problem of SPARSKIT's MATGEN/FDIF): with h = 1/(nx+1),
4 + βh on the diagonal, -1 - βh for the upwind neighbour along the first
axis and -1 for the other three.  Values are unsymmetric, the pattern is
symmetric."""

from benchmark.matrices import stencil


def build(nx: int, beta: float):
    bh = beta / (nx + 1)
    return stencil((nx, nx), 4.0 + bh,
                   [(0, -1, -1.0 - bh), (0, 1, -1.0),
                    (1, -1, -1.0), (1, 1, -1.0)])
