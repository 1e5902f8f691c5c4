"""Core runtime layer: resources handle, bitset, serialization, logging.

TPU-native analog of the reference's ``cpp/include/raft/core`` (SURVEY.md
§2.1). There are no streams or BLAS handles here — XLA owns scheduling — so
the handle shrinks to mesh/device/RNG/logger state plus a lazy slot registry
retained for comms injection.
"""

from raft_tpu.core.resources import Resources, DeviceResources
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.serialize import save_npy, load_npy, serialize_mdspan, deserialize_mdspan
from raft_tpu.core.logger import logger, set_level
from raft_tpu.core.interruptible import Interruptible, synchronize
from raft_tpu.core.device_ndarray import auto_convert_output, cai_wrapper, device_ndarray
from raft_tpu.core.pipeline import Prefetcher, overlap, resolve_depth

__all__ = [
    "Resources",
    "device_ndarray",
    "auto_convert_output",
    "cai_wrapper",
    "DeviceResources",
    "Bitset",
    "save_npy",
    "load_npy",
    "serialize_mdspan",
    "deserialize_mdspan",
    "logger",
    "set_level",
    "Interruptible",
    "synchronize",
    "Prefetcher",
    "overlap",
    "resolve_depth",
]
