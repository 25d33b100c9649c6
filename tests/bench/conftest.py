import os
import sys

CHIP = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                    "chip")
sys.path.insert(0, os.path.abspath(CHIP))
