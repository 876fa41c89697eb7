from .log import Log

__all__ = ["Log"]
