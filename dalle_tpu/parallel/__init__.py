from .mesh import (build_mesh, single_device_mesh, shard_batch,
                   shard_stacked_batch, batch_spec, replicated,
                   local_batch_size, use_mesh)
from .backend import (DistributedBackend, JaxBackend, DummyBackend, BACKENDS,
                      wrap_arg_parser, set_backend_from_args, using_backend)
from .partition import (DEFAULT_RULES, commit_to_mesh, make_param_shardings,
                        shard_params, spec_for, constrain)
from .ring_attention import ring_attention, shard_seq
