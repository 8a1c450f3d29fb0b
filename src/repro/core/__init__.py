"""Core library: the paper's contribution (PowerSGD + EF-SGD) as composable
JAX modules."""

from repro.core.dist import (
    AXIS,
    AxisBackend,
    CollectiveBackend,
    CollectiveStats,
    MeshCtx,
    SimBackend,
    SINGLE,
)
from repro.core.simmesh import SimMesh
from repro.core.matrixize import MatrixSpec, default_spec
from repro.core.engine import CompressOut, Encoded, MatrixPayloads, Transport
from repro.core.powersgd import PowerSGDConfig, compress_aggregate, init_state
from repro.core.compressors import (
    Compressor,
    IdentityCompressor,
    PowerSGDCompressor,
    UnbiasedRankK,
    RandomBlock,
    RandomK,
    SignNorm,
    TopK,
    SpectralAtomo,
    ExactRankK,
    make_compressor,
)
from repro.core import error_feedback
