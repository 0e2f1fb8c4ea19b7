"""The 7-point 3-D Laplacian on an nx × nx × nx grid, Dirichlet boundaries:
6 on the diagonal, -1 for each of the six neighbours.  This is PETSc KSP
tutorial ex45's operator on the grid's interior: ex45 keeps each boundary
node as a row that holds only its diagonal, and multiplies every entry by
the constant h; here the boundary rows are eliminated and the constant is
left out."""

from benchmark.matrices import stencil


def build(nx: int):
    return stencil((nx, nx, nx), 6.0,
                   [(axis, step, -1.0) for axis in range(3)
                    for step in (-1, 1)])
