from .ops import se_scale, se_scale_impl  # noqa: F401
from .ref import se_scale_ref  # noqa: F401
