from tpu_sandbox.parallel.collectives import (  # noqa: F401
    CollectiveGroup,
    CompressedAllReduce,
)
from tpu_sandbox.parallel.data_parallel import DataParallel  # noqa: F401
from tpu_sandbox.parallel.expert import MoeMlp  # noqa: F401
from tpu_sandbox.parallel.pipeline import PipelineParallel  # noqa: F401
from tpu_sandbox.parallel.pjit_engine import PjitEngine, megatron_rules  # noqa: F401
from tpu_sandbox.parallel.ring_attention import make_ring_attention, ring_attention  # noqa: F401
from tpu_sandbox.parallel.seq_parallel import SeqParallel  # noqa: F401
from tpu_sandbox.parallel.ulysses import make_ulysses_attention, ulysses_attention  # noqa: F401
from tpu_sandbox.parallel.flash_ring import (  # noqa: F401
    flash_ring_attention,
    make_flash_ring_attention,
)
