from scalable_agent_tpu.models.agent import (  # noqa: F401
    ImpalaAgent, init_params, make_step_fn)
from scalable_agent_tpu.models.core import (  # noqa: F401
    LSTMCore, RecurrentCore)
from scalable_agent_tpu.models.retention import (  # noqa: F401
    PowerRetentionStack)
from scalable_agent_tpu.models.latent_moe import (  # noqa: F401
    LatentMoEDims, LatentMoEStack)
from scalable_agent_tpu.models.hybrid_attention import (  # noqa: F401
    HybridAttentionDims, HybridAttentionStack)
from scalable_agent_tpu.models.sequence import SequenceAgent  # noqa: F401
from scalable_agent_tpu.models.torsos import (  # noqa: F401
    DeepResNetTorso, ShallowTorso, TORSOS)
from scalable_agent_tpu.models.instruction import (  # noqa: F401
    InstructionEncoder, hash_instruction, MAX_INSTRUCTION_LEN, VOCAB_SIZE)
