"""Put the benchmark's modules and the program's sources on the path."""
import os
import sys

BENCH = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.abspath(BENCH))
sys.path.insert(0, os.path.abspath(os.path.join(BENCH, "..", "src")))
