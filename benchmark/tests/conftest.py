import os
import sys

# run by hand from the repo's root: pytest benchmark/tests
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
