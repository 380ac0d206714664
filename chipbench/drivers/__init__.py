"""One driver per kind of deployment; a configuration's ``kind`` names it."""
