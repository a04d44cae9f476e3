import os
import sys

# the program under test, as the benchmark's own entry point finds it
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
