from repro.utils.timing import span, timed
from repro.utils.trees import tree_bytes, tree_param_count
