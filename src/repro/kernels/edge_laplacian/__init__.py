"""edge_laplacian — fused Pallas kernels for the ADMM constraint matvec.

``edge_laplacian``: candidate-edge weights g → n×n Laplacian L(g) (the
scatter-heavy half of every ``A_op``); ``edge_quadform``: n×n dual block →
per-edge quadratic forms ⟨∂L/∂g_l, P⟩ (the gather-heavy half of ``AT_op``).
Layout follows ``kernels/gossip_mix``: ``ref.py`` pure-jnp oracle,
``kernel.py`` the Pallas bodies, ``ops.py`` jitted public wrappers. Neither
kernel compiles for the TPU (see ``kernel.py``); there they raise.
"""
from . import kernel, ops, ref  # noqa: F401

__all__ = ["kernel", "ops", "ref"]
