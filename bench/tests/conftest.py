import os
import sys

# the program under test lives in the repository's src/
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
