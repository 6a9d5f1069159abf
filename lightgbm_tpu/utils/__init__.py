from .log import (LightGBMError, get_verbosity, log_debug, log_fatal,
                  log_info, log_warning, set_verbosity)

__all__ = [
    "LightGBMError", "get_verbosity", "log_debug", "log_fatal", "log_info",
    "log_warning", "set_verbosity",
]
