"""The self-tests run from anywhere: put the repo's root (the parent of
``graftbench/``) and this directory on the import path."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(os.path.dirname(HERE)), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)
