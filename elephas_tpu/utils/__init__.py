from .functional_utils import (add_params, divide_by, get_neutral,
                               subtract_params, tree_add, tree_divide,
                               tree_scale, tree_subtract, tree_zeros_like)
from .model_utils import (LossModelTypeMapper, ModelType, ModelTypeEncoder,
                          as_enum)
from .rwlock import RWLock
from .serialization import dict_to_model, model_to_dict
from .sockets import determine_master, receive, send
from .dataset_utils import (encode_label, from_labeled_points, lp_to_dataset,
                            to_dataset, to_labeled_points)
from .checkpoint import CheckpointManager
from .faults import (FaultEvent, FaultPlan, InjectedFault, active_plan,
                     clear_plan, fault_site, install_plan)
from .tracing import StepTimer, profiler_trace
