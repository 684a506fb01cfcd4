"""Put the benchmark directory and the program's sources on the path."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.dirname(HERE)
sys.path.insert(0, ONCHIP)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(ONCHIP)),
                                "src"))
