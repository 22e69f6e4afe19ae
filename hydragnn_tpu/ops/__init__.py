from . import aggregate, segment
